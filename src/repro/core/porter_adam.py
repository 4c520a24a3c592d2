"""PORTER-Adam: a beyond-paper variant that Adam-preconditions the tracked
gradient.

The paper's Algorithm 1 uses a plain SGD step `X -= eta * V`.  Since `v_i`
tracks the *global* gradient at every agent (the tracking identity
v-bar == g-bar is preserved -- preconditioning happens after tracking), each
agent can apply a local Adam update to its own tracked estimate:

    m_i = b1 m_i + (1-b1) v_i
    s_i = b2 s_i + (1-b2) v_i^2
    x_i = x_i + gamma (M_x - Q_x)_i - eta * m-hat_i / (sqrt(s-hat_i) + eps)

Caveat (why this is "beyond-paper" and not covered by Theorems 2-4): the
update is a *nonlinear* function of v_i, so the mean iterate is no longer an
exact function of v-bar -- agents' moments can drift apart.  Empirically
(tests/test_porter_adam.py) consensus still contracts because m_i, s_i are
driven by the tracked (therefore agreeing) v_i's, and the preconditioner
accelerates the ill-conditioned MLP problem.  A proof is future work; the
implementation exists so the framework can train real models with the
optimizer people actually use.

Communication is *identical* to PORTER (same two compressed streams via the
same :class:`repro.core.comm_round.CommRound` engine -- the parameter round
is ``engine.step`` with the preconditioned update as the descent direction);
moments are purely local state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .comm_round import CommRound
from .compression import Compressor
from .gossip import MixFn
from .porter import (LossFn, PorterConfig, PorterState, _agent_gradient,
                     _resolve_engine, consensus_error, porter_init)

__all__ = ["PorterAdamState", "porter_adam_init", "make_porter_adam_step"]


class PorterAdamState(NamedTuple):
    base: PorterState
    m: Any          # first moment, agent-stacked
    s: Any          # second moment, agent-stacked


def porter_adam_init(params, n_agents: int, w=None,
                     plane_dtype=None) -> PorterAdamState:
    base = porter_init(params, n_agents, w=w, plane_dtype=plane_dtype)
    # Adam moments are purely local (never hit a plane or the wire) and the
    # second moment is variance-fragile, so they stay f32 under bf16 planes.
    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros_like(l, dtype=jnp.float32), base.v)
    return PorterAdamState(base=base, m=zeros, s=zeros)


def porter_adam_step(
    cfg: PorterConfig,
    loss_fn: LossFn,
    mixer: Optional[MixFn],
    compressor: Optional[Compressor],
    state: PorterAdamState,
    batch: Any,
    key: jax.Array,
    b1: float = 0.9,
    b2: float = 0.999,
    adam_eps: float = 1e-8,
    compress_fn=None,
    engine: Optional[CommRound] = None,
) -> Tuple[PorterAdamState, Dict[str, jax.Array]]:
    st = state.base
    n = jax.tree_util.tree_leaves(st.x)[0].shape[0]
    _, k_noise, k_cv, k_cx = jax.random.split(key, 4)
    eng = _resolve_engine(engine, mixer, compressor, compress_fn)

    # gradients + tracking: identical to Algorithm 1 lines 4-12
    with jax.named_scope("oracle"):
        agent_keys = jax.random.split(k_noise, n)
        grad_fn = functools.partial(_agent_gradient, cfg, loss_fn)
        losses, g = jax.vmap(grad_fn)(st.x, batch, agent_keys)
        g = jax.tree_util.tree_map(lambda l: l.astype(cfg.grad_dtype), g)

    if eng.overlap:
        # the x-side exchange reads only (st.x, st.q_x) -- independent of
        # the track update AND the Adam moments -- so both collectives are
        # in flight before the local moment math runs (see CommRound.overlap)
        k_cv, sr_v = eng.sr_split(k_cv, (st.q_v, st.m_v, st.v))
        k_cx, sr_x = eng.sr_split(k_cx, (st.q_x, st.m_x, st.x))
        c_v, wc_v = eng.exchange(k_cv, st.v, st.q_v, t=st.step)
        c_x, wc_x = eng.exchange(k_cx, st.x, st.q_x, t=st.step)
        v, q_v, m_v = eng.track_update(c_v, wc_v, st.v, st.q_v, st.m_v, g,
                                       st.g_prev, cfg.gamma, sr_key=sr_v)
    else:
        c_x = wc_x = sr_x = None
        v, q_v, m_v = eng.track(k_cv, st.v, st.q_v, st.m_v, g, st.g_prev,
                                cfg.gamma, t=st.step)

    # local Adam moments on the tracked gradient
    step_no = (st.step + 1).astype(jnp.float32)
    bc1 = 1.0 - b1 ** step_no
    bc2 = 1.0 - b2 ** step_no
    m = jax.tree_util.tree_map(lambda m0, vv: b1 * m0 + (1 - b1) * vv,
                               state.m, v)
    s = jax.tree_util.tree_map(
        lambda s0, vv: b2 * s0 + (1 - b2) * jnp.square(vv), state.s, v)
    update = jax.tree_util.tree_map(
        lambda mm, ss: (mm / bc1) / (jnp.sqrt(ss / bc2) + adam_eps), m, s)

    # parameter round: Algorithm 1 lines 13-14 with the preconditioned update
    if eng.overlap:
        x, q_x, m_x = eng.step_update(c_x, wc_x, st.x, st.q_x, st.m_x,
                                      update, cfg.gamma, cfg.eta,
                                      sr_key=sr_x)
    else:
        x, q_x, m_x = eng.step(k_cx, st.x, st.q_x, st.m_x, update,
                               cfg.gamma, cfg.eta, t=st.step)

    new_base = PorterState(x=x, v=v, q_x=q_x, q_v=q_v, g_prev=g, m_x=m_x,
                           m_v=m_v, step=st.step + 1)
    with jax.named_scope("step.metrics"):
        metrics = {"loss": jnp.mean(losses),
                   "consensus_x": consensus_error(x),
                   "consensus_v": consensus_error(v),
                   "wire_bytes": jnp.asarray(2.0 * eng.wire_bytes(st.x),
                                             jnp.float32)}
    return PorterAdamState(base=new_base, m=m, s=s), metrics


def make_porter_adam_step(cfg: PorterConfig, loss_fn: LossFn, mixer: MixFn,
                          compressor: Compressor, backend: str = "auto",
                          interpret: Optional[bool] = None, **adam_kw):
    engine = CommRound(compressor=compressor, mixer=mixer,
                       compress_fn=adam_kw.pop("compress_fn", None),
                       backend=backend, interpret=interpret)
    return functools.partial(porter_adam_step, cfg, loss_fn, None, None,
                             engine=engine, **adam_kw)
