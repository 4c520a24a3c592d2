"""Public jit'd wrappers for the Pallas kernels.

Handles shape plumbing (flatten -> pad to tile multiples -> 2D tile grid ->
un-pad, where a kernel needs it; the EF and SR kernels grid over an array
as it lies) and the interpret switch: on CPU kernels execute in
``interpret=True`` mode, which runs the kernel body in Python/XLA-CPU and is
what the allclose tests validate; on TPU the same code lowers to Mosaic.

The ef_* wrappers are additionally shard_map-safe: the comm-round engine's
per-shard plane path (:func:`repro.kernels.flatten.plane_apply`) invokes
them once *per (agent shard x model shard)* inside ``shard_map``, so they
must stay shape-polymorphic and free of global-device assumptions (no mesh
queries, no collectives) -- each call sees only its shard's block.

Use ``repro.kernels.ops`` from the algorithm layer; never call the raw
kernels directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import block_topk as _bt
from . import ef_update as _ef
from . import rwkv6_chunk as _rw
from . import sr_cast as _srk
from . import ssd_chunk as _ssd
from . import smooth_clip as _sc
from . import wire_pack as _wp
from . import ref

__all__ = ["smooth_clip", "block_topk", "ef_track", "ef_step", "ef_gossip",
           "rwkv6_scan", "ssd_scan", "default_interpret",
           "sr_cast", "sr_cast_ref",
           "wire_topk_pack", "wire_topk_unpack",
           "wire_qsgd_pack", "wire_qsgd_unpack"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_2d(flat: jax.Array, tile: int):
    d = flat.shape[0]
    pad = (-d) % tile
    padded = jnp.pad(flat, (0, pad))
    return padded.reshape(-1, tile), d


@functools.partial(jax.jit, static_argnames=("tau", "sigma", "interpret"))
def smooth_clip(x: jax.Array, tau: float, noise=None, sigma: float = 0.0,
                interpret: bool | None = None) -> jax.Array:
    """Fused Clip_tau(x) (+ sigma*noise) over an arbitrary-shape array."""
    interpret = default_interpret() if interpret is None else interpret
    shape = x.shape
    x2d, d = _pad_2d(x.reshape(-1), _sc.TILE)
    partials = _sc.sumsq(x2d, interpret=interpret)
    nrm = jnp.sqrt(jnp.sum(partials))
    factor = (tau / (tau + nrm)).astype(jnp.float32)
    if noise is not None:
        n2d, _ = _pad_2d(noise.reshape(-1), _sc.TILE)
        y2d = _sc.scale(x2d, factor, n2d, jnp.asarray(sigma, jnp.float32),
                        interpret=interpret)
    else:
        y2d = _sc.scale(x2d, factor, interpret=interpret)
    return y2d.reshape(-1)[:d].reshape(shape)


@functools.partial(jax.jit, static_argnames=("frac", "interpret"))
def block_topk(x: jax.Array, frac: float,
               interpret: bool | None = None) -> jax.Array:
    """rho = frac compressor: per-2048-block magnitude top-k (kernel)."""
    interpret = default_interpret() if interpret is None else interpret
    shape = x.shape
    x2d, d = _pad_2d(x.reshape(-1), _bt.BLOCK)
    k = max(int(round(frac * _bt.BLOCK)), 1)
    y2d = _bt.block_topk(x2d, k, interpret=interpret)
    return y2d.reshape(-1)[:d].reshape(shape)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def wire_topk_pack(rows: jax.Array, k: int, interpret: bool | None = None):
    """Fused select+pack: (nb, PACK_BLOCK) -> (bf16 vals, uint16 idx).

    One pass per window (bisection threshold + one-hot compaction); the
    indices are window-local so uint16 always suffices.  This is the wire
    payload the codec gossip executors ship (4 bytes per kept element).
    """
    interpret = default_interpret() if interpret is None else interpret
    vals, idx = _wp.topk_pack(rows, k, interpret=interpret)
    return vals, idx.astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wire_topk_unpack(vals: jax.Array, idx: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
    """Receiver side: packed segments -> dense f32 (nb, PACK_BLOCK)."""
    interpret = default_interpret() if interpret is None else interpret
    return _wp.topk_unpack(vals, idx.astype(jnp.int32), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def wire_qsgd_pack(rows: jax.Array, key: jax.Array, levels: int,
                   interpret: bool | None = None):
    """Per-window QSGD quantize + uint32 bit-pack: (nb, PACK_BLOCK) ->
    (uint32 words (nb, W), f32 scale (nb, 1)).  The stochastic-rounding
    noise is drawn from ``key`` outside the kernel so the jnp reference
    (core.wire_formats.qsgd_pack_ref) quantizes identically."""
    interpret = default_interpret() if interpret is None else interpret
    noise = jax.random.uniform(key, rows.shape, jnp.float32)
    return _wp.qsgd_pack(rows.astype(jnp.float32), noise, levels,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def wire_qsgd_unpack(word: jax.Array, scale: jax.Array, levels: int,
                     interpret: bool | None = None) -> jax.Array:
    """Receiver side: bit-packed codes + scales -> dense f32 windows."""
    interpret = default_interpret() if interpret is None else interpret
    return _wp.qsgd_unpack(word, scale, levels, interpret=interpret)


def _sr_bits(key: jax.Array, shape) -> jax.Array:
    """The u32 words a stochastic-rounding cast of ``shape`` consumes."""
    with jax.named_scope("engine.sr_bits"):
        return jax.random.bits(key, shape, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sr_cast(x: jax.Array, key: jax.Array,
            interpret: bool | None = None) -> jax.Array:
    """Stochastic-rounding f32 -> bf16 cast over an arbitrary-shape array.

    Random bits come from ``key`` outside the kernel, in ``x``'s own shape,
    so this and :func:`sr_cast_ref` round bit-identically for the same key
    (the pattern wire_qsgd_pack uses for its dither noise).
    """
    interpret = default_interpret() if interpret is None else interpret
    bits = _sr_bits(key, x.shape)
    return _srk.sr_cast(x.astype(jnp.float32), bits, interpret=interpret)


@jax.jit
def sr_cast_ref(x: jax.Array, key: jax.Array) -> jax.Array:
    """jnp reference for :func:`sr_cast` (same bits draw, no pallas)."""
    bits = _sr_bits(key, x.shape)
    return _srk.sr_cast_ref(x.astype(jnp.float32), bits)


@jax.jit
def sr_cast_leaf(x: jax.Array, key: jax.Array) -> jax.Array:
    """Sharding-preserving SR cast: no plane padding, bits drawn in ``x``'s
    own shape.  The ref engine's writeback uses this on whole state leaves
    -- the :func:`sr_cast` / :func:`sr_cast_ref` pair reshapes through
    padded planes, which reshards an agent-sharded leaf and puts the
    flattened buffer (and its u32 bits) on the wire.  The key folds per
    leading-axis row, so each agent row's bits derive from its own key and
    the SPMD partitioner generates them shard-locally (a single
    whole-array draw from a replicated key lowers with partitioner
    collectives on the agent mesh)."""
    if x.ndim == 0:
        return _srk.sr_cast_ref(x.astype(jnp.float32), _sr_bits(key, ()))
    with jax.named_scope("engine.sr_bits"):
        ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(x.shape[0]))
        bits = jax.vmap(
            lambda kk, row: jax.random.bits(kk, row.shape, jnp.uint32))(ks, x)
    return _srk.sr_cast_ref(x.astype(jnp.float32), bits)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def ef_track(q, m, v, c, wc, g, gp, gamma, interpret: bool | None = None,
             out_dtype=None):
    """Fused Algorithm-1 lines 11-12 (q += c; m += wc; v update).

    out_dtype: force all three outputs to one dtype (the engine requests
    f32 here and stochastically rounds the writeback to bf16 buffers);
    ``None`` keeps each output in its state operand's dtype.
    """
    interpret = default_interpret() if interpret is None else interpret
    return tuple(_ef.ef_track(q, m, v, c, wc, g, gp, gamma,
                              interpret=interpret, out_dtype=out_dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def ef_step(q, m, x, c, wc, v, gamma, eta, interpret: bool | None = None,
            out_dtype=None):
    """Fused Algorithm-1 lines 13-14 (q += c; m += wc; x update)."""
    interpret = default_interpret() if interpret is None else interpret
    return tuple(_ef.ef_step(q, m, x, c, wc, v, gamma, eta,
                             interpret=interpret, out_dtype=out_dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def ef_gossip(q, m, y, c, wc, gamma, scale=1.0, interpret: bool | None = None,
              out_dtype=None):
    """Fused CHOCO/Soteria update (q += s*c; m += s*wc; y += gamma*(m-q))."""
    interpret = default_interpret() if interpret is None else interpret
    return tuple(_ef.ef_gossip(q, m, y, c, wc, gamma, scale,
                               interpret=interpret, out_dtype=out_dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_scan(r, k, v, logw, u, s0, interpret: bool | None = None):
    """RWKV6 chunked linear-attention scan (kernel).

    r,k,v,logw: (B,S,H,N) with S % 16 == 0; u: (H,N); s0: (B,H,N,N).
    Returns (o: (B,S,H,N) f32, s_final: (B,H,N,N) f32).  The VMEM-resident
    state makes this the TPU-native replacement for the lax.scan chunk loop
    in repro.nn.ssm (which round-trips the state through HBM every chunk).
    """
    interpret = default_interpret() if interpret is None else interpret
    b, s_len, h, n = r.shape
    c = _rw.CHUNK
    assert s_len % c == 0, "pad sequence to a multiple of 16"
    nc = s_len // c

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, nc, c, n)

    u_bh = jnp.tile(u, (b, 1))
    o, s_fin = _rw.rwkv6_chunk(to_bh(r), to_bh(k), to_bh(v), to_bh(logw),
                               u_bh, s0.reshape(b * h, n, n),
                               interpret=interpret)
    o = o.reshape(b, h, s_len, n).transpose(0, 2, 1, 3)
    return o, s_fin.reshape(b, h, n, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(xh, bmat, cmat, dla, h0, interpret: bool | None = None):
    """Mamba2 SSD chunked scan (kernel).

    xh: (B,S,H,P); bmat/cmat: (B,S,N); dla: (B,S,H) per-step log-decay;
    h0: (B,H,P,N).  S % 64 == 0.  Returns (y: (B,S,H,P), h_fin: (B,H,P,N)).
    """
    interpret = default_interpret() if interpret is None else interpret
    b, s_len, h, p = xh.shape
    n = bmat.shape[-1]
    c = _ssd.CHUNK
    assert s_len % c == 0, "pad sequence to a multiple of 64"
    nc = s_len // c

    xh_bh = xh.transpose(0, 2, 1, 3).reshape(b * h, nc, c, p)
    dla_bh = dla.transpose(0, 2, 1).reshape(b * h, nc, c, 1)
    bm = jnp.broadcast_to(bmat[:, None], (b, h, s_len, n)).reshape(
        b * h, nc, c, n)
    cm = jnp.broadcast_to(cmat[:, None], (b, h, s_len, n)).reshape(
        b * h, nc, c, n)
    y, h_fin = _ssd.ssd_chunk(xh_bh, bm, cm, dla_bh,
                              h0.reshape(b * h, p, n), interpret=interpret)
    y = y.reshape(b, h, s_len, p).transpose(0, 2, 1, 3)
    return y, h_fin.reshape(b, h, p, n)
