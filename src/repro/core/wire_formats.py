"""Bit-packed wire formats: the one shared constants module for the packed
gossip payloads (ISSUE-6; the PR-3 drift-bug class motivated centralizing).

The executor (:mod:`repro.core.gossip`), the Pallas kernels
(:mod:`repro.kernels.wire_pack`), and the byte model all import the layout
from here, so none of them can drift from the others:

* ``topk_bits``  -- per PACK_BLOCK window, the ``k_b = max(round(frac *
  PACK_BLOCK), 1)`` largest-|.| elements as two contiguous segments:
  bf16 values and uint16 *window-local* indices (PACK_BLOCK < 2**16, so
  16 bits always suffice).  4 bytes per kept element -- exactly 8x denser
  than the dense f32 window at the same sparsity, and exactly 4x fewer
  wire bytes than dense at frac = 0.25.  int32 remains the logical index
  type on the unpack side.

* ``qsgd_bits``  -- per PACK_BLOCK window, QSGD codes bit-packed into
  uint32 words plus one f32 scale.  Each element's field is
  ``bits = ceil(log2(levels + 1)) + 1`` wide (magnitude code in
  [0, levels] plus a sign bit); ``32 // bits`` fields per word.  At
  ``levels = 7`` the field is exactly 4 bits -- a 16-state signed
  alphabet ("s=16" in the benchmarks) -- so the code payload is exactly
  8x denser than dense f32; the per-window f32 scale is accounted
  separately as overhead (payload ratio 8.0x, total ~7.97x at
  PACK_BLOCK = 2048).

Quantization granularity: the wire codec normalizes *per window* (the
scale that ships is per PACK_BLOCK window), unlike
:func:`repro.core.compression.qsgd` which normalizes over the whole
vector.  Per-window QSGD is still a Definition-3 compressor with
``omega = min(sqrt(PACK_BLOCK)/s, PACK_BLOCK/s**2)`` (errors and energies
add over windows), and the engine applies the *round-tripped* increment
locally (``c := unpack(pack(delta))``), so the ``m = W q`` invariant is
exact regardless of what the codec does to the values.

bf16 rho note (Definition 3): the ``topk_bits`` value payload is bf16, so
the round-tripped increment carries an extra relative rounding error of at
most 2**-8 per kept value; the effective contraction is
``rho' >= rho * (1 - 2**-8)**2`` -- far inside the slack of every contract
test, but stated here (and in EXPERIMENTS.md) rather than hidden.

The selection threshold is the same value-range bisection the
:mod:`repro.kernels.block_topk` kernel uses; it lives here (pure jnp, legal
inside Pallas kernel bodies) so selection and packing share one routine.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PACK_BLOCK",
    "N_BISECT_ITERS",
    "TOPK_VALUE_DTYPE",
    "TOPK_INDEX_DTYPE",
    "WIRE_FORMATS",
    "WIRE_MODES",
    "WireFormat",
    "bisect_threshold",
    "topk_keep",
    "qsgd_bits",
    "qsgd_elems_per_word",
    "qsgd_words_per_window",
    "qsgd_window_omega",
    "topk_pack_ref",
    "topk_unpack_ref",
    "qsgd_pack_ref",
    "qsgd_unpack_ref",
    "make_wire_format",
    "measured_pack_nbytes",
    "codec_collective_bytes",
    "to_windows",
    "from_windows",
]

# packed wire format selection window (16 x 128 lanes).  gossip.py and
# kernels/block_topk.py re-export this; it is defined only here.
PACK_BLOCK = 2048

# bisection iterations for the top-k threshold (f32 has 24 mantissa bits)
N_BISECT_ITERS = 24

TOPK_VALUE_DTYPE = jnp.bfloat16
TOPK_INDEX_DTYPE = jnp.uint16   # window-local; PACK_BLOCK < 2**16

# spec-level wire knob values (ExperimentSpec.wire)
WIRE_MODES = ("dense", "packed_bits")

# registered payload layouts (one per compressor family)
WIRE_FORMATS = ("topk_bits", "qsgd_bits")


def topk_keep(frac: float) -> int:
    """Kept elements per PACK_BLOCK window at sparsity ``frac``."""
    return max(int(round(frac * PACK_BLOCK)), 1)


def qsgd_bits(levels: int) -> int:
    """Field width: magnitude code in [0, levels] plus one sign bit."""
    return int(np.ceil(np.log2(levels + 1))) + 1


def qsgd_elems_per_word(levels: int) -> int:
    return 32 // qsgd_bits(levels)


def qsgd_words_per_window(levels: int) -> int:
    epw = qsgd_elems_per_word(levels)
    return -(-PACK_BLOCK // epw)


def qsgd_window_omega(levels: int) -> float:
    """QSGD relative variance at the window size (per-window normalization)."""
    return float(min(np.sqrt(PACK_BLOCK) / levels, PACK_BLOCK / levels ** 2))


# ---------------------------------------------------------------------------
# Shared selection threshold (used verbatim inside the Pallas kernels)
# ---------------------------------------------------------------------------

def bisect_threshold(a: jax.Array, k, axis=None) -> jax.Array:
    """Threshold keeping >= k of the values in ``a`` via value bisection.

    ``a``: non-negative magnitudes (any shape, reduced globally).  Returns
    the scalar ``lo`` with ``count(a >= lo) >= k`` after N_BISECT_ITERS
    halvings -- log2-many compare+count sweeps, each a fully vectorized VPU
    pass, which is the TPU replacement for sort/radix-select.  Pure jnp, so
    it runs identically inside a Pallas kernel body, under vmap (per-row
    thresholds), and in the jnp reference codecs.

    ``axis``: reduce along that axis only, keeping it as size 1 -- one
    threshold per row, the same halvings as a vmap over rows, written with
    selects so a TPU kernel can run it on a block of rows.
    """
    if axis is not None:
        hi = jnp.max(a, axis=axis, keepdims=True)

        def rows(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            take = jnp.sum((a >= mid).astype(jnp.int32), axis=axis,
                           keepdims=True) >= k
            return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

        lo, _ = jax.lax.fori_loop(0, N_BISECT_ITERS, rows,
                                  (jnp.zeros_like(hi), hi))
        return lo

    hi = jnp.max(a)
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(jnp.int32))
        # too few kept -> threshold too high; too many -> raise it
        return jax.lax.cond(cnt >= k,
                            lambda: (mid, hi),
                            lambda: (lo, mid))

    lo, hi = jax.lax.fori_loop(0, N_BISECT_ITERS, body, (lo, hi))
    return lo


# ---------------------------------------------------------------------------
# jnp reference codecs (the numerical oracles for kernels/wire_pack.py; also
# what the gossip executors run off-TPU)
# ---------------------------------------------------------------------------

def to_windows(flat: jax.Array) -> jax.Array:
    """Pad a flat vector to PACK_BLOCK windows: (d,) -> (nb, PACK_BLOCK)."""
    d = flat.shape[0]
    pad = (-d) % PACK_BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, PACK_BLOCK)


def from_windows(rows: jax.Array, d: int, shape=None) -> jax.Array:
    out = rows.reshape(-1)[:d]
    return out if shape is None else out.reshape(shape)


def topk_pack_ref(rows: jax.Array, k: int):
    """Per-window top-k pack: (nb, PACK_BLOCK) -> (bf16 (nb, k), u16 (nb, k)).

    Selection matches the kernel: bisection threshold, then the first k
    qualifying elements in *index order* (ties beyond k drop
    deterministically).  The packed segments are index-ordered, not
    magnitude-sorted -- the unpacked window is identical either way.
    """
    rows32 = rows.astype(jnp.float32)
    a = jnp.abs(rows32)
    nb = rows32.shape[0]
    th = jax.vmap(lambda r: bisect_threshold(r, k))(a)          # (nb,)
    keep = a >= th[:, None]
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    sel = keep & (rank < k)
    col = jnp.where(sel, rank, k)                               # spill -> k
    row_ids = jnp.broadcast_to(jnp.arange(nb)[:, None], col.shape)
    vals = jnp.zeros((nb, k + 1), jnp.float32)
    vals = vals.at[row_ids, col].set(rows32)[:, :k]
    pos = jnp.broadcast_to(jnp.arange(PACK_BLOCK)[None, :], col.shape)
    idx = jnp.zeros((nb, k + 1), jnp.int32)
    idx = idx.at[row_ids, col].set(pos)[:, :k]
    return vals.astype(TOPK_VALUE_DTYPE), idx.astype(TOPK_INDEX_DTYPE)


def topk_unpack_ref(vals: jax.Array, idx: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """(bf16 (nb, k), u16 (nb, k)) -> dense (nb, PACK_BLOCK) window."""
    nb, k = vals.shape
    row_ids = jnp.broadcast_to(jnp.arange(nb)[:, None], (nb, k))
    out = jnp.zeros((nb, PACK_BLOCK), jnp.float32)
    out = out.at[row_ids, idx.astype(jnp.int32)].add(vals.astype(jnp.float32))
    return out.astype(dtype)


def qsgd_pack_ref(key: jax.Array, rows: jax.Array, levels: int):
    """Per-window QSGD quantize + bit-pack.

    (nb, PACK_BLOCK) -> (uint32 words (nb, W), f32 scale (nb, 1)) with
    W = qsgd_words_per_window(levels).  Stochastic rounding draws one
    uniform per element from ``key``; the scale already folds in the
    1/(1+omega) Definition-3 contraction so unpack is sign*code*scale.
    """
    bits = qsgd_bits(levels)
    epw = qsgd_elems_per_word(levels)
    words = qsgd_words_per_window(levels)
    rows32 = rows.astype(jnp.float32)
    nb = rows32.shape[0]
    norm = jnp.sqrt(jnp.sum(rows32 * rows32, axis=1)) + 1e-30    # (nb,)
    y = jnp.abs(rows32) / norm[:, None] * levels
    lo = jnp.floor(y)
    prob = y - lo
    u = jax.random.uniform(key, rows32.shape)
    code = (lo + (u < prob)).astype(jnp.uint32)                  # [0, levels]
    sign = (rows32 < 0).astype(jnp.uint32)
    field = code | (sign << jnp.uint32(bits - 1))
    pad = words * epw - PACK_BLOCK
    field = jnp.pad(field, ((0, 0), (0, pad))).reshape(nb, words, epw)
    word = jnp.zeros((nb, words), jnp.uint32)
    for e in range(epw):                                         # static OR
        word = word | (field[:, :, e] << jnp.uint32(bits * e))
    omega = qsgd_window_omega(levels)
    scale = (norm / (levels * (1.0 + omega))).astype(jnp.float32)
    return word, scale[:, None]


def qsgd_unpack_ref(word: jax.Array, scale: jax.Array, levels: int,
                    dtype=jnp.float32) -> jax.Array:
    """(uint32 (nb, W), f32 (nb, 1)) -> dense (nb, PACK_BLOCK) window."""
    bits = qsgd_bits(levels)
    epw = qsgd_elems_per_word(levels)
    nb, words = word.shape
    mag_mask = jnp.uint32(2 ** (bits - 1) - 1)
    field_mask = jnp.uint32(2 ** bits - 1)
    cols = []
    for e in range(epw):
        f = (word >> jnp.uint32(bits * e)) & field_mask
        code = (f & mag_mask).astype(jnp.float32)
        sgn = 1.0 - 2.0 * (f >> jnp.uint32(bits - 1)).astype(jnp.float32)
        cols.append(sgn * code)
    vals = jnp.stack(cols, axis=2).reshape(nb, words * epw)[:, :PACK_BLOCK]
    return (vals * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Format registry: layout + byte model in one object
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireFormat:
    """One bit-packed payload layout: codec + byte model, inseparable.

    Attributes:
      name: "topk_bits" | "qsgd_bits".
      deterministic: True when ``pack`` ignores its key (top-k).
      payload_bytes_per_window / overhead_bytes_per_window: exact bytes
        each PACK_BLOCK window puts on the wire (overhead = per-window
        scales; the acceptance ratios count payload, totals include both).
      pack: (key, rows (nb, PACK_BLOCK)) -> tuple of wire buffers.
      unpack: (*buffers, dtype=...) -> (nb, PACK_BLOCK) dense window.
      n_buffers: how many wire buffers ``pack`` returns (the codec gossip
        executors ship each one through its own collective, so this is the
        per-leaf collective multiplier the static analyzer budgets
        against -- see :class:`repro.core.gossip.GossipBudget`).
    """

    name: str
    deterministic: bool
    payload_bytes_per_window: int
    overhead_bytes_per_window: int
    pack: Callable
    unpack: Callable
    n_buffers: int = 2

    def windows(self, d: int) -> int:
        return -(-int(d) // PACK_BLOCK)

    def payload_bytes(self, d: int) -> float:
        return float(self.windows(d) * self.payload_bytes_per_window)

    def overhead_bytes(self, d: int) -> float:
        return float(self.windows(d) * self.overhead_bytes_per_window)

    def buffer_bytes(self, d: int) -> float:
        """Modeled nbytes of one agent's packed buffers for a d-vector."""
        return self.payload_bytes(d) + self.overhead_bytes(d)


def make_wire_format(compressor_name: str, *, frac: Optional[float] = None,
                     levels: Optional[int] = None, use_pallas: bool = False,
                     interpret: Optional[bool] = None) -> WireFormat:
    """The wire format for a compressor family.

    ``use_pallas`` routes pack/unpack through the fused
    :mod:`repro.kernels.wire_pack` kernels (``interpret`` as in kernels.ops);
    otherwise the jnp reference codecs above run (XLA-fused, the oracle).
    """
    if compressor_name in ("top_k", "block_top_k"):
        if frac is None:
            raise ValueError("topk_bits wire format needs frac")
        k = topk_keep(frac)
        if use_pallas:
            from ..kernels import ops as _ops

            def pack(key, rows, _k=k):
                del key
                return _ops.wire_topk_pack(rows, _k, interpret=interpret)

            def unpack(vals, idx, dtype=jnp.float32):
                return _ops.wire_topk_unpack(vals, idx, interpret=interpret
                                             ).astype(dtype)
        else:
            def pack(key, rows, _k=k):
                del key
                return topk_pack_ref(rows, _k)

            unpack = topk_unpack_ref
        return WireFormat(
            name="topk_bits", deterministic=True,
            payload_bytes_per_window=4 * k,      # bf16 value + u16 index
            overhead_bytes_per_window=0,
            pack=pack, unpack=unpack, n_buffers=2)
    if compressor_name == "qsgd":
        if levels is None:
            raise ValueError("qsgd_bits wire format needs levels")
        words = qsgd_words_per_window(levels)
        if use_pallas:
            from ..kernels import ops as _ops

            def pack(key, rows, _l=levels):
                return _ops.wire_qsgd_pack(rows, key, _l, interpret=interpret)

            def unpack(word, scale, dtype=jnp.float32, _l=levels):
                return _ops.wire_qsgd_unpack(word, scale, _l,
                                             interpret=interpret).astype(dtype)
        else:
            def pack(key, rows, _l=levels):
                return qsgd_pack_ref(key, rows, _l)

            def unpack(word, scale, dtype=jnp.float32, _l=levels):
                return qsgd_unpack_ref(word, scale, _l, dtype)
        return WireFormat(
            name="qsgd_bits", deterministic=False,
            payload_bytes_per_window=4 * words,  # bit-packed uint32 codes
            overhead_bytes_per_window=4,         # one f32 scale per window
            pack=pack, unpack=unpack, n_buffers=2)
    raise ValueError(
        f"compressor {compressor_name!r} has no registered bit-packed wire "
        f"format; have {WIRE_FORMATS} (top_k/block_top_k -> topk_bits, "
        "qsgd -> qsgd_bits)")


def measured_pack_nbytes(fmt: WireFormat, d: int) -> int:
    """Actual nbytes of the shipped buffers for a d-vector: traced shapes
    via jax.eval_shape on the codec itself, so the measurement cannot drift
    from what the executor ships (the model in :meth:`WireFormat
    .buffer_bytes` is the cross-check, not the source)."""
    nb = fmt.windows(d)
    rows = jax.ShapeDtypeStruct((nb, PACK_BLOCK), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    bufs = jax.eval_shape(lambda k, r: fmt.pack(k, r), key, rows)
    return sum(int(np.prod(b.shape)) * np.dtype(b.dtype).itemsize
               for b in jax.tree_util.tree_leaves(bufs))


def measured_weight_nbytes(fmt: WireFormat) -> int:
    """Measured nbytes the push-sum weight scalar adds to one shipped buffer
    set.  The codec gossip executors bitcast the exact f32 weight increment
    into words of the *last* wire buffer's dtype and append them to its
    flattened payload (:mod:`repro.core.gossip`); this traces that buffer's
    dtype via ``jax.eval_shape`` on the codec itself -- like
    :func:`measured_pack_nbytes`, the measurement cannot drift from what the
    executor ships."""
    rows = jax.ShapeDtypeStruct((1, PACK_BLOCK), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    bufs = jax.eval_shape(lambda k, r: fmt.pack(k, r), key, rows)
    itemsize = np.dtype(jax.tree_util.tree_leaves(bufs)[-1].dtype).itemsize
    if itemsize not in (2, 4):
        raise ValueError(
            f"no push-sum weight word layout for a {itemsize}-byte wire "
            "buffer dtype")
    return (4 // itemsize) * itemsize


def codec_collective_bytes(fmt: WireFormat, mode: str, n_agents: int,
                           d: int) -> float:
    """Per-round link bytes for one agent buffer under a codec-aware
    executor, matching :func:`repro.core.gossip.gossip_wire_bytes`'s
    conventions: 'ring' ships each agent's packed buffers to its live
    neighbors (one shift at n=2, else two); 'packed' all-gathers every
    agent's packed buffers."""
    per_agent = fmt.buffer_bytes(d)
    if mode == "ring":
        shifts = 1.0 if n_agents == 2 else 2.0
        return shifts * per_agent
    if mode == "packed":
        return float(n_agents) * per_agent
    raise ValueError(f"no codec wire accounting for gossip mode {mode!r}")
