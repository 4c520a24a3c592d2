"""Pallas TPU kernels: bit-packed wire buffers for the gossip payloads.

Layouts (single source of truth: :mod:`repro.core.wire_formats`):

* top-k   -- per PACK_BLOCK window, selection *and* packing in one fused
  pass: the bisection threshold from :func:`wire_formats.bisect_threshold`
  (the same routine kernels/block_topk.py zeroes with), then compaction of
  the k survivors into contiguous (bf16 value, index) segments.  TPUs have
  no VMEM scatter, so compaction is a one-hot matmul: rank each survivor by
  its running count (first k in index order; threshold ties beyond k drop
  deterministically) and contract the window against the rank indicator --
  an MXU pass instead of a serial gather.

* qsgd    -- per-window stochastic quantization to codes in [0, levels]
  plus a sign bit, then shift/OR of ``32 // bits`` fields per uint32 word.
  The uniform noise comes in as an operand (generated from the caller's
  key) so the kernel stays deterministic given its inputs and the jnp
  reference (wire_formats.qsgd_pack_ref) is bit-comparable.

Unpack kernels invert each layout on the receiver: top-k scatters via the
transposed one-hot matmul, qsgd shifts/masks the fields back out.

Blocking, shaped by what the TPU lowering accepts:

* top-k packs ``ROWS`` windows per grid step, each seen as a
  ``(16, 128)`` tile stack (a free reshape of the window).  The running
  count is a triangular matmul within each 128-lane chunk plus a matmul
  over the chunk sums before it, and the one-hot is built one 128-lane
  chunk at a time as a ``(k_pad, 128)`` slab, so its VMEM stays bounded at
  any k (a whole window's ``(PACK_BLOCK, k)`` one-hot is 4 MiB at k = 512).
  The slot axis is padded to ``k_pad``, a multiple of 128, and trimmed
  outside the kernel.  Unpack mirrors it per window row.  Every matmul runs
  at HIGHEST precision, so counts, values and positions are exact; rows
  are merged into the output block by select, since the lowering stores
  whole blocks only.
* qsgd works on the ``(epw, windows, words)`` transpose of the windows, in
  which the fields that share a word sit on the leading axis: packing is an
  OR over that axis, with no lane shuffles.  The per-window norm comes in
  as an operand, computed outside by the reference's own expression.

The jit'd public wrappers live in :mod:`repro.kernels.ops`
(wire_topk_pack / wire_topk_unpack / wire_qsgd_pack / wire_qsgd_unpack).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.wire_formats import (PACK_BLOCK, TOPK_VALUE_DTYPE,
                                     bisect_threshold, qsgd_bits,
                                     qsgd_elems_per_word,
                                     qsgd_words_per_window,
                                     qsgd_window_omega)

BLOCK = PACK_BLOCK
CHUNK = 128                  # lanes per one-hot slab
ROWS = 8                     # top-k windows per grid step
QSGD_ROWS = 32               # qsgd windows per grid step
_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # contract the last dims: a @ b.T


def _pad_slots(k: int) -> int:
    return -(-k // CHUNK) * CHUNK


# ---------------------------------------------------------------------------
# top-k: fused select + compact
# ---------------------------------------------------------------------------

def _topk_pack_kernel(x_ref, v_ref, i_ref, *, k: int):
    r, kp = v_ref.shape
    n_chunks = BLOCK // CHUNK
    iota = jax.lax.broadcasted_iota
    # inclusive prefix count: within a 128-lane chunk, and over the chunks
    # before it (0/1 operands, counts <= 2048: exact in f32)
    tri = (iota(jnp.int32, (CHUNK, CHUNK), 0)
           <= iota(jnp.int32, (CHUNK, CHUNK), 1)).astype(jnp.float32)
    before = (iota(jnp.int32, (n_chunks, n_chunks), 1)
              < iota(jnp.int32, (n_chunks, n_chunks), 0)).astype(jnp.float32)
    slot = iota(jnp.int32, (kp, CHUNK), 0).astype(jnp.float32)
    lane = iota(jnp.int32, (1, CHUNK), 1).astype(jnp.float32)
    row = iota(jnp.int32, (r, kp), 0)

    def window(w, acc):
        x = x_ref[w].astype(jnp.float32)                  # (chunks, CHUNK)
        a = jnp.abs(x)
        thresh = bisect_threshold(a, k, axis=(0, 1))      # shared selection
        keep = (a >= thresh).astype(jnp.float32)
        rank = (jnp.dot(keep, tri, precision=_HI,
                        preferred_element_type=jnp.float32)
                + jnp.dot(before, jnp.broadcast_to(
                    jnp.sum(keep, axis=1, keepdims=True), keep.shape),
                    precision=_HI, preferred_element_type=jnp.float32))
        # slot of each kept element, -1 for the rest (first k, by index)
        dest = jnp.where((keep > 0) & (rank <= k), rank - 1.0, -1.0)
        vals = jnp.zeros((1, kp), jnp.float32)
        pos = jnp.zeros((1, kp), jnp.float32)
        for c in range(n_chunks):
            # onehot[s, e] = 1 iff element e of chunk c lands in slot s
            onehot = (slot == dest[c:c + 1]).astype(jnp.float32)
            vals += jax.lax.dot_general(x[c:c + 1], onehot, _NT,
                                        precision=_HI,
                                        preferred_element_type=jnp.float32)
            pos += jax.lax.dot_general(lane + float(c * CHUNK), onehot, _NT,
                                       precision=_HI,
                                       preferred_element_type=jnp.float32)
        # rows are merged by select: the lowering stores whole blocks only
        return (jnp.where(row == w, vals, acc[0]),
                jnp.where(row == w, pos, acc[1]))

    zeros = jnp.zeros((r, kp), jnp.float32)
    vals, pos = jax.lax.fori_loop(0, r, window, (zeros, zeros))
    v_ref[...] = vals
    i_ref[...] = pos.astype(jnp.int32)


def topk_pack(x2d: jax.Array, k: int, interpret: bool = False):
    """(blocks, BLOCK) -> (bf16 values (blocks, k), int32 indices).

    Exactly k slots per window (bisection keeps >= k; the compaction caps
    at the first k in index order).  Indices are window-local; the wire
    layer narrows them to uint16 (wire_formats.TOPK_INDEX_DTYPE).
    """
    blocks = x2d.shape[0]
    kp = _pad_slots(k)
    r = min(ROWS, blocks)
    out = pl.BlockSpec((r, kp), lambda i: (i, 0))
    vals, idx = pl.pallas_call(
        functools.partial(_topk_pack_kernel, k=k),
        grid=(pl.cdiv(blocks, r),),
        in_specs=[pl.BlockSpec((r, BLOCK // CHUNK, CHUNK),
                               lambda i: (i, 0, 0))],
        out_specs=(out, out),
        out_shape=(jax.ShapeDtypeStruct((blocks, kp), jnp.float32),
                   jax.ShapeDtypeStruct((blocks, kp), jnp.int32)),
        interpret=interpret,
    )(x2d.reshape(blocks, BLOCK // CHUNK, CHUNK))
    return vals[:, :k].astype(TOPK_VALUE_DTYPE), idx[:, :k]


def _topk_unpack_kernel(v_ref, i_ref, o_ref):
    r, kp = v_ref.shape
    elem = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, kp), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (r, CHUNK), 0)

    def window(w, acc):
        vals = v_ref[pl.ds(w, 1), :]                      # (1, kp) f32
        idx = i_ref[pl.ds(w, 1), :]                       # (1, kp) int32
        out = []
        for c in range(BLOCK // CHUNK):
            # onehot[e, r] = 1 iff slot r holds element e of this chunk
            onehot = (elem + c * CHUNK == idx).astype(jnp.float32)
            dense = jax.lax.dot_general(vals, onehot, _NT, precision=_HI,
                                        preferred_element_type=jnp.float32)
            out.append(jnp.where(row == w, dense, acc[c]))
        return tuple(out)

    zeros = jnp.zeros((r, CHUNK), jnp.float32)
    chunks = jax.lax.fori_loop(0, r, window,
                               (zeros,) * (BLOCK // CHUNK))
    for c, dense in enumerate(chunks):
        o_ref[:, c * CHUNK:(c + 1) * CHUNK] = dense


def topk_unpack(vals: jax.Array, idx: jax.Array,
                interpret: bool = False) -> jax.Array:
    """(values (blocks, k), int32 indices) -> dense f32 (blocks, BLOCK)."""
    blocks, k = vals.shape
    kp = _pad_slots(k)
    # padded slots point nowhere (index -1), so they scatter nothing
    vals = jnp.pad(vals.astype(jnp.float32), ((0, 0), (0, kp - k)))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, kp - k)),
                  constant_values=-1)
    r = min(ROWS, blocks)
    blk = pl.BlockSpec((r, kp), lambda i: (i, 0))
    return pl.pallas_call(
        _topk_unpack_kernel,
        grid=(pl.cdiv(blocks, r),),
        in_specs=[blk, blk],
        out_specs=pl.BlockSpec((r, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, BLOCK), jnp.float32),
        interpret=interpret,
    )(vals, idx)


# ---------------------------------------------------------------------------
# qsgd: quantize + shift/OR bit-pack over the (epw, windows, words) view
# ---------------------------------------------------------------------------

def _to_fields(a: jax.Array, levels: int) -> jax.Array:
    """(blocks, BLOCK) -> (epw, blocks, words): field e of word j on axis 0.

    The tail of the last word is zero-padded, as in the reference."""
    epw = qsgd_elems_per_word(levels)
    words = qsgd_words_per_window(levels)
    a = jnp.pad(a, ((0, 0), (0, words * epw - BLOCK)))
    return a.reshape(a.shape[0], words, epw).transpose(2, 0, 1)


def _qsgd_pack_kernel(x_ref, u_ref, n_ref, w_ref, *, levels: int):
    bits = qsgd_bits(levels)
    x = x_ref[...].astype(jnp.float32)                    # (epw, R, words)
    u = u_ref[...].astype(jnp.float32)
    y = jnp.abs(x) / n_ref[...][None] * levels
    lo = jnp.floor(y)
    code = (lo + (u < (y - lo))).astype(jnp.int32)        # [0, levels]
    sign = (x < 0).astype(jnp.int32)
    field = code | (sign << (bits - 1))
    word = field[0]
    for e in range(1, x.shape[0]):                        # static OR chain
        word = word | (field[e] << (bits * e))
    w_ref[...] = word


def qsgd_pack(x2d: jax.Array, noise2d: jax.Array, levels: int,
              interpret: bool = False):
    """(blocks, BLOCK) + uniform noise -> (uint32 words, f32 (blocks, 1)).

    ``noise2d``: U[0,1) per element (the stochastic-rounding draws),
    generated by the caller from its PRNG key so kernel and jnp reference
    quantize identically.
    """
    blocks = x2d.shape[0]
    epw = qsgd_elems_per_word(levels)
    words = qsgd_words_per_window(levels)
    x32 = x2d.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x32 * x32, axis=1)) + 1e-30      # (blocks,)
    r = min(QSGD_ROWS, blocks)
    fields = pl.BlockSpec((epw, r, words), lambda i: (0, i, 0))
    word = pl.pallas_call(
        functools.partial(_qsgd_pack_kernel, levels=levels),
        grid=(pl.cdiv(blocks, r),),
        in_specs=[fields, fields, pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, words), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, words), jnp.int32),
        interpret=interpret,
    )(_to_fields(x32, levels), _to_fields(noise2d, levels), norm[:, None])
    omega = qsgd_window_omega(levels)
    scale = (norm / (levels * (1.0 + omega))).astype(jnp.float32)
    return (jax.lax.bitcast_convert_type(word, jnp.uint32),
            scale[:, None])


def _qsgd_unpack_kernel(w_ref, s_ref, o_ref, *, levels: int):
    bits = qsgd_bits(levels)
    word = w_ref[...]                                     # (R, words) int32
    scale = s_ref[...]                                    # (R, 1)
    mag_mask = 2 ** (bits - 1) - 1
    field_mask = 2 ** bits - 1
    for e in range(o_ref.shape[0]):
        f = (word >> (bits * e)) & field_mask
        code = (f & mag_mask).astype(jnp.float32)
        sgn = 1.0 - 2.0 * (f >> (bits - 1)).astype(jnp.float32)
        o_ref[e] = (sgn * code * scale).astype(o_ref.dtype)


def qsgd_unpack(word: jax.Array, scale: jax.Array, levels: int,
                interpret: bool = False) -> jax.Array:
    """(uint32 (blocks, W), f32 (blocks, 1)) -> dense f32 (blocks, BLOCK)."""
    blocks, words = word.shape
    epw = qsgd_elems_per_word(levels)
    r = min(QSGD_ROWS, blocks)
    out = pl.pallas_call(
        functools.partial(_qsgd_unpack_kernel, levels=levels),
        grid=(pl.cdiv(blocks, r),),
        in_specs=[pl.BlockSpec((r, words), lambda i: (i, 0)),
                  pl.BlockSpec((r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((epw, r, words), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((epw, blocks, words), jnp.float32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(word, jnp.int32), scale)
    return out.transpose(1, 2, 0).reshape(blocks, words * epw)[:, :BLOCK]
