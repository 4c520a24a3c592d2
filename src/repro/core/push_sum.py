"""DP-CSGP: differentially-private compressed gossip over *directed* graphs.

The paper's recipe (per-sample clipping + Gaussian perturbation + compressed
error-feedback gossip, Algorithm 1 Option I) assumes a doubly-stochastic
mixing matrix -- every agent hears exactly the agents it is heard by.  Real
fleets lose links one way at a time; DP-CSGP (arXiv 2512.13583, PAPERS.md)
extends the recipe to directed, possibly unbalanced graphs via
**column-stochastic** weights and **push-sum** correction:

* Each agent carries a scalar push-sum weight ``xw_i`` (init 1) mixed with
  the *same* column-stochastic ``W_t`` as the parameters.  Column sums of 1
  conserve total mass (``1^T W = 1^T``), so while the raw iterates drift
  toward the graph's Perron vector, the de-biased ratio ``z = x / xw`` stays
  an unbiased consensus estimate -- gradients are evaluated at ``z``, not
  ``x``.
* The weight plane runs the *same* EF/gossip recursion as the params
  (surrogate ``q_w``, mirror ``m_w``) but its increment is **never
  compressed**: ``cw = xw - q_w`` exactly.  Compressing it would break the
  column-mass invariant the de-biasing relies on.  The composed weight
  update is ``xw' = ((1-gamma) I + gamma W_t) xw`` -- still
  column-stochastic, so weights stay strictly positive and converge to
  ``n * pi`` (the Perron vector of the window product).

State: :class:`PorterState`'s buffers plus the three ``(n,)`` weight planes
(``xw``, ``q_w``, ``m_w``).  Communication and both fused updates are
delegated to :meth:`repro.core.comm_round.CommRound.step_ps`, whose
executors ship the weight inside the collectives the param round already
issues (an extra flat column for dense/ring, +4 bitcast bytes on codec
buffers) -- directed gossip adds zero communication ops.

Reduction sanity: with a doubly-stochastic ``W`` (row sums 1 too) the
weight increments are identically zero, ``xw`` stays exactly 1, and
``z = x / 1`` is bit-identical to ``x`` -- DP-CSGP's trajectory coincides
with PORTER-DP's (pinned by tests/test_push_sum.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import clipping
from .comm_round import CommRound, resolve_engine
from .compression import Compressor
from .gossip import MixFn, make_dense_mixer
from .porter import (LossFn, PorterConfig, _agent_gradient, consensus_error)

__all__ = [
    "DpCsgpState",
    "dp_csgp_init",
    "dp_csgp_step",
    "debias",
]

# Push-sum weights are strictly positive in exact arithmetic (positive
# diagonals keep every agent a fraction of its own mass); the floor only
# guards the division against fp underflow on pathologically long windows.
_WEIGHT_FLOOR = 1e-12


class DpCsgpState(NamedTuple):
    x: Any
    v: Any
    q_x: Any
    q_v: Any
    g_prev: Any
    m_x: Any
    m_v: Any
    xw: jax.Array     # (n,) push-sum weights
    q_w: jax.Array    # (n,) weight surrogate (EF)
    m_w: jax.Array    # (n,) weight mixing mirror
    step: jax.Array


def debias(x, xw):
    """z = x / xw, broadcasting the (n,) weight over each leaf's agent axis.

    With ``xw`` exactly 1 (doubly-stochastic mixing) this is bit-identity
    (IEEE division by 1.0), which is what makes the PORTER-DP reduction
    exact.
    """
    w = jnp.maximum(xw.astype(jnp.float32), _WEIGHT_FLOOR)
    return jax.tree_util.tree_map(
        lambda l: (l / w.reshape((-1,) + (1,) * (l.ndim - 1))
                   .astype(l.dtype)).astype(l.dtype), x)


def _zeros_like_f(tree, dtype):
    return jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, dtype), tree)


def dp_csgp_init(params: Any, n_agents: int, w: Optional[np.ndarray] = None,
                 w0: Optional[np.ndarray] = None,
                 buffer_dtype: Any = jnp.float32,
                 plane_dtype: Any = None) -> DpCsgpState:
    """Initialize from a single replica; X^0 = x0 1^T, weights all 1.

    Unlike :func:`repro.core.porter.porter_init`, the mirrors *must* be
    materialized against the actual round-0 matrix: ``m = W q`` with
    ``q_x = x0 1^T`` and ``q_w = 1`` gives ``m_x = W x0 1^T`` and
    ``m_w = W 1`` -- the no-mix shortcut (``m_x = x``) assumes row sums of
    1, which column-stochastic tables do not have.  ``w0`` is the resolved
    round-0 matrix (the facade passes ``schedule.ws[0]`` / ``topology.w``);
    an explicit ``w`` from the registry's uniform ``init(params, n, w)``
    protocol takes precedence.  With neither, the doubly-stochastic
    shortcut applies (and is exact for every undirected topology).

    ``plane_dtype``: storage dtype for the param-sized EF buffers (see
    :func:`repro.core.porter.porter_init`).  The three (n,) push-sum weight
    planes (xw, q_w, m_w) always stay f32 -- rounding the de-biasing mass
    would break the column-mass invariant ``1^T xw = n``.
    """
    x = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (n_agents,) + p.shape), params)
    pdt = None if plane_dtype is None else jnp.dtype(plane_dtype)
    zeros = _zeros_like_f(x, buffer_dtype if pdt is None else pdt)
    ones = jnp.ones((n_agents,), jnp.float32)
    weff = w if w is not None else w0
    if weff is None:
        m_x, m_w = x, ones
    else:
        weff = np.asarray(weff, np.float64)
        if weff.ndim == 3:           # a stacked schedule table: round 0
            weff = weff[0]
        m_x = make_dense_mixer(weff)(x)
        m_w = jnp.asarray(weff.sum(axis=1), jnp.float32)  # W @ 1 (row sums)
    q_x = x
    if pdt is not None:
        q_x = jax.tree_util.tree_map(lambda l: l.astype(pdt), x)
        m_x = jax.tree_util.tree_map(lambda l: l.astype(pdt), m_x)
    return DpCsgpState(x=x, v=zeros, q_x=q_x, q_v=zeros, g_prev=zeros,
                       m_x=m_x, m_v=zeros, xw=ones, q_w=ones, m_w=m_w,
                       step=jnp.zeros((), jnp.int32))


def dp_csgp_step(
    cfg: PorterConfig,
    loss_fn: LossFn,
    mixer: Optional[MixFn],
    compressor: Optional[Compressor],
    state: DpCsgpState,
    batch: Any,
    key: jax.Array,
    compress_fn=None,
    engine: Optional[CommRound] = None,
) -> Tuple[DpCsgpState, Dict[str, jax.Array]]:
    """One DP-CSGP iteration over all agents (pure; jit/pjit-able).

    Identical to :func:`repro.core.porter.porter_step` except (1) the
    gradient oracle evaluates at the de-biased point ``z = x / xw``, (2) the
    x-side round is the push-sum :meth:`CommRound.step_ps` carrying the
    weight planes, and (3) ``wire_bytes`` charges the weight's extra bytes
    on the x stream.  The v-side (gradient-tracking) round needs no
    de-biasing -- tracking accumulates gradient *differences*, which the
    column-stochastic mix conserves in total mass like any other mass.
    """
    eng = resolve_engine(engine, mixer, compressor, compress_fn)
    n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    _, k_noise, k_cv, k_cx = jax.random.split(key, 4)

    # ---- stochastic gradients at the de-biased consensus estimate ---------
    with jax.named_scope("oracle"):
        z = debias(state.x, state.xw)
        agent_keys = jax.random.split(k_noise, n)
        grad_fn = functools.partial(_agent_gradient, cfg, loss_fn)
        losses, g = jax.vmap(grad_fn)(z, batch, agent_keys)
        g = jax.tree_util.tree_map(lambda l: l.astype(cfg.grad_dtype), g)

    # ---- comm rounds: plain track + push-sum step -------------------------
    if eng.overlap:
        # same overlap legality as PORTER: the x-side exchange reads only
        # (x, q_x, xw, q_w), which the v-side update never touches
        k_cv, sr_v = eng.sr_split(k_cv, (state.q_v, state.m_v, state.v))
        k_cx, sr_x = eng.sr_split(k_cx, (state.q_x, state.m_x, state.x))
        c_v, wc_v = eng.exchange(k_cv, state.v, state.q_v, t=state.step)
        c_x, wc_x, cw, wcw = eng.exchange_ps(
            k_cx, state.x, state.q_x, state.xw, state.q_w, t=state.step)
        v, q_v, m_v = eng.track_update(c_v, wc_v, state.v, state.q_v,
                                       state.m_v, g, state.g_prev, cfg.gamma,
                                       sr_key=sr_v)
        x, q_x, m_x, xw, q_w, m_w = eng.step_ps_update(
            c_x, wc_x, cw, wcw, state.x, state.q_x, state.m_x, v,
            state.xw, state.q_w, state.m_w, cfg.gamma, cfg.eta, sr_key=sr_x)
    else:
        v, q_v, m_v = eng.track(k_cv, state.v, state.q_v, state.m_v, g,
                                state.g_prev, cfg.gamma, t=state.step)
        x, q_x, m_x, xw, q_w, m_w = eng.step_ps(
            k_cx, state.x, state.q_x, state.m_x, v, state.xw, state.q_w,
            state.m_w, cfg.gamma, cfg.eta, t=state.step)

    new_state = DpCsgpState(x=x, v=v, q_x=q_x, q_v=q_v, g_prev=g,
                            m_x=m_x, m_v=m_v, xw=xw, q_w=q_w, m_w=m_w,
                            step=state.step + 1)
    with jax.named_scope("step.metrics"):
        metrics = {
            "loss": jnp.mean(losses),
            # consensus on the de-biased estimates: the raw x drift toward
            # the Perron vector is push-sum working, not disagreement
            "consensus_x": consensus_error(debias(x, xw)),
            "consensus_v": consensus_error(v),
            "v_norm": clipping.tree_global_norm(v) / np.sqrt(n),
            # v stream is a plain round, x stream carries the weight plane
            "wire_bytes": jnp.asarray(
                eng.wire_bytes(state.x)
                + eng.wire_bytes(state.x, push_sum=True), jnp.float32),
        }
    return new_state, metrics
