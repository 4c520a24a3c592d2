"""Pallas TPU kernel: fused PORTER error-feedback / tracking update.

Algorithm 1 lines 11-14 perform, per agent, a chain of parameter-sized AXPYs:

    q  +=  c                       (surrogate accumulate)
    m  +=  wc                      (mixing-mirror accumulate)
    v   =  v + gamma*(m - q) + g - g_prev      (gradient track)
    x   =  x + gamma*(mx - qx) - eta*v         (parameter step)

Issued as separate jnp ops this is ~13 HBM reads + 4 writes of parameter-
sized buffers; fused it is 7 reads + 4 writes in a single pass.  On a
bandwidth-bound v5e (819 GB/s) that is the dominant cost of a PORTER step
outside the model itself, which is why this is a kernel (see EXPERIMENTS.md
§Perf for the measured effect on the memory roofline term).

This kernel fuses the V-side (``ef_track``):   q+=c; m+=wc; v = v + gamma*
(m-q) + g - gp;   the X-side (``ef_step``) is the same shape with the
gradient terms swapped for -eta*v.  ``ef_gossip`` is the two-term tail of
the same family (q+=c; m+=wc; y = y + gamma*(m-q)) and serves the
CHOCO-SGD / SoteriaFL compressed-gossip updates through the comm-round
engine (core/comm_round.py), one launch per state leaf over all agents
(kernels/flatten.py).  :func:`grid_call` grids a kernel over the leaf as it
lies in memory -- its last two dims tiled, the leading ones merged, which
costs no copy on the TPU.  Flattening a leaf into padded ``(tiles, 8*1024)``
planes first, as the kernels did, is a relayout copy of every operand.
The scalars ride in SMEM.

Mixed precision: inputs may arrive as bf16 buffers (2 B/element resident
state); every kernel upcasts to f32 *inside* the block, accumulates in f32,
and writes each output in the dtype of its corresponding state plane
(q/m/x/v/y), so an f32 master-param plane never narrows just because the EF
planes beside it are bf16.  ``out_dtype`` overrides all output dtypes at
once -- the engine requests f32 outputs and applies stochastic rounding
(kernels/sr_cast.py) on the writeback to sub-f32 buffers, keeping the EF
drift unbiased instead of round-to-nearest biased.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_BLOCK = 2048            # lanes per block when the last dim is wider
BLOCK_ELEMS = 64 * 1024      # elements per block and operand: 256 KiB in f32


def _view(shape):
    """``(lead, rows, cols)`` view of an array: the last two dims stay the
    tiled ones, the leading dims merge (free on the TPU, no relayout)."""
    if len(shape) < 2:
        return (1, 1, math.prod(shape))
    return (math.prod(shape[:-2]), shape[-2], shape[-1])


def grid_call(kernel, arrays, scalars, n_out, out_dtype, interpret):
    """Grid an elementwise ``kernel`` over same-shape ``arrays`` in place.

    The arrays keep their own layout -- no flatten, no pad -- and the grid
    walks ``(rows, cols)`` blocks of at most ``BLOCK_ELEMS`` elements
    (multiples of (16, 128), or the whole dim) with partial edge blocks.
    The first ``n_out`` arrays give the outputs' shapes and dtypes
    (``out_dtype`` overrides the dtype); ``scalars``: f32 values passed as
    ``(1, 1)`` SMEM operands after the arrays.
    """
    shape = arrays[0].shape
    if any(a.shape != shape for a in arrays):
        raise ValueError(f"grid_call needs same-shape operands, got "
                         f"{[a.shape for a in arrays]}")
    lead, rows, cols = _view(shape)
    bc = min(cols, LANE_BLOCK)
    lanes = -(-bc // 128) * 128          # VMEM pads the lane dim to 128
    br = min(rows, max(16, BLOCK_ELEMS // lanes // 16 * 16))
    blk = pl.BlockSpec((None, br, bc), lambda a, i, j: (a, i, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    view = (lead, rows, cols)
    outs = pl.pallas_call(
        kernel,
        grid=(lead, pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[blk] * len(arrays) + [smem] * len(scalars),
        out_specs=[blk] * n_out,
        out_shape=[jax.ShapeDtypeStruct(
            view, a.dtype if out_dtype is None else out_dtype)
            for a in arrays[:n_out]],
        interpret=interpret,
    )(*[a.reshape(view) for a in arrays],
      *[jnp.asarray(s, jnp.float32).reshape(1, 1) for s in scalars])
    return [o.reshape(shape) for o in outs]


def _track_kernel(q_ref, m_ref, v_ref, c_ref, wc_ref, g_ref, gp_ref,
                  gamma_ref, q_out, m_out, v_out):
    q = q_ref[...].astype(jnp.float32) + c_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32) + wc_ref[...].astype(jnp.float32)
    gamma = gamma_ref[0, 0]
    v = (v_ref[...].astype(jnp.float32) + gamma * (m - q)
         + g_ref[...].astype(jnp.float32) - gp_ref[...].astype(jnp.float32))
    q_out[...] = q.astype(q_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def ef_track(q, m, v, c, wc, g, gp, gamma, interpret: bool = False,
             out_dtype=None):
    """(q,m,v) update of Algorithm 1 lines 11-12.  Same-shape inputs."""
    return grid_call(_track_kernel, (q, m, v, c, wc, g, gp), (gamma,), 3,
                       out_dtype, interpret)


def _step_kernel(q_ref, m_ref, x_ref, c_ref, wc_ref, v_ref,
                 gamma_ref, eta_ref, q_out, m_out, x_out):
    q = q_ref[...].astype(jnp.float32) + c_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32) + wc_ref[...].astype(jnp.float32)
    x = (x_ref[...].astype(jnp.float32) + gamma_ref[0, 0] * (m - q)
         - eta_ref[0, 0] * v_ref[...].astype(jnp.float32))
    q_out[...] = q.astype(q_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    x_out[...] = x.astype(x_out.dtype)


def ef_step(q, m, x, c, wc, v, gamma, eta, interpret: bool = False,
            out_dtype=None):
    """(q,m,x) update of Algorithm 1 lines 13-14.  Same-shape inputs."""
    return grid_call(_step_kernel, (q, m, x, c, wc, v), (gamma, eta), 3,
                       out_dtype, interpret)


def _gossip_kernel(q_ref, m_ref, y_ref, c_ref, wc_ref, gamma_ref, scale_ref,
                   q_out, m_out, y_out):
    scale = scale_ref[0, 0]
    q = (q_ref[...].astype(jnp.float32)
         + scale * c_ref[...].astype(jnp.float32))
    m = (m_ref[...].astype(jnp.float32)
         + scale * wc_ref[...].astype(jnp.float32))
    y = y_ref[...].astype(jnp.float32) + gamma_ref[0, 0] * (m - q)
    q_out[...] = q.astype(q_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    y_out[...] = y.astype(y_out.dtype)


def ef_gossip(q, m, y, c, wc, gamma, scale=1.0, interpret: bool = False,
              out_dtype=None):
    """(q,m,y) CHOCO/Soteria update: q += s*c; m += s*wc; y += gamma*(m-q).

    ``scale`` is 1 for CHOCO-SGD and the SoteriaFL shift stepsize alpha for
    shifted compression.  Same-shape tensor inputs.
    """
    return grid_call(_gossip_kernel, (q, m, y, c, wc), (gamma, scale), 3,
                       out_dtype, interpret)
