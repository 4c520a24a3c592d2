"""Baseline algorithms the paper compares against (Table 1 and Section 5).

* ``dsgd``          decentralized SGD with gossip averaging (no tracking, no
                    EF, optionally clipped) -- the naive adaptation.
* ``choco``         CHOCO-SGD [KSJ19]: compressed gossip with surrogate
                    mirrors, no gradient tracking.
* ``dp_sgd``        centralized DP-SGD [ACG+16] -- Table 1's single-server
                    baseline (utility phi_m reference point).
* ``soteriafl``     SoteriaFL-SGD [LZLC22]: server/client LDP with *shifted*
                    compression -- the paper's Section-5 head-to-head.

All share the agent-stacked pytree layout of :mod:`repro.core.porter` so the
same data pipeline, loss functions and metrics apply.  The compressed
algorithms route their communication through the comm-round engine
(:class:`repro.core.comm_round.CommRound`): CHOCO's surrogate/mirror round
is ``engine.gossip_apply``, SoteriaFL's shifted compression is
``engine.shift`` -- there is no hand-rolled ``q += c; m += Wc`` body left in
this module.

Metrics schema (uniform across algorithms, so benchmarks/ablation.py can
compare them on equal footing):

    loss         mean agent loss
    consensus_x  ||X - x-bar 1^T||_F^2   (decentralized algorithms)
    wire_bytes   model-level bytes crossing links per round (all agents)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import clipping
from .comm_round import CommRound, resolve_engine
from .compression import Compressor
from .gossip import MixFn, apply_mixer, gossip_wire_bytes
from .porter import LossFn, average_params, consensus_error, perturb

__all__ = [
    "DsgdState", "dsgd_init", "dsgd_step",
    "ChocoState", "choco_init", "choco_step",
    "DpSgdState", "dpsgd_init", "dpsgd_step",
    "SoteriaState", "soteria_init", "soteria_step",
]


def _tree(op, *trees):
    return jax.tree_util.tree_map(op, *trees)


def _stack(params, n):
    return _tree(lambda p: jnp.broadcast_to(p, (n,) + p.shape), params)


def _param_count(tree, n_agents: int) -> int:
    return sum(int(l.size) // n_agents
               for l in jax.tree_util.tree_leaves(tree))


def _dp_gradient(loss_fn, params, batch, key, tau, clip_mode, sigma_p):
    g, loss = clipping.clipped_grad_accumulate(loss_fn, params, batch, tau,
                                               clip_mode)
    return loss, perturb(g, key, sigma_p)


# ---------------------------------------------------------------------------
# DSGD
# ---------------------------------------------------------------------------

class DsgdState(NamedTuple):
    x: Any
    step: jax.Array


def dsgd_init(params, n_agents: int) -> DsgdState:
    return DsgdState(x=_stack(params, n_agents),
                     step=jnp.zeros((), jnp.int32))


def dsgd_step(eta: float, gamma: float, loss_fn: LossFn, mixer: MixFn,
              state: DsgdState, batch, key,
              tau: Optional[float] = None, clip_mode: str = "smooth",
              sigma_p: float = 0.0, dp: bool = False
              ) -> Tuple[DsgdState, Dict[str, jax.Array]]:
    """X^{t+1} = X + gamma X(W - I) - eta G   (uncompressed gossip)."""
    n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    keys = jax.random.split(key, n)

    def agent_grad(p, b, k):
        if dp:
            return _dp_gradient(loss_fn, p, b, k, tau, clip_mode, sigma_p)
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if tau is not None:
            g = clipping.tree_clip(g, tau, clip_mode)
        return loss, g

    with jax.named_scope("oracle"):
        losses, g = jax.vmap(agent_grad)(state.x, batch, keys)
    # W_t X; the step counter selects the round's matrix under a schedule
    mixed = apply_mixer(mixer, state.x, state.step)
    x = _tree(lambda x0, wx, gg: x0 + gamma * (wx - x0) - eta * gg,
              state.x, mixed, g)
    # uncompressed gossip of the full parameter buffer every round
    frac = getattr(mixer, "wire_frac", None)
    wire = gossip_wire_bytes(getattr(mixer, "wire_mode", "dense"), n,
                             _param_count(state.x, n),
                             frac=1.0 if frac is None else frac)
    with jax.named_scope("step.metrics"):
        metrics = {"loss": jnp.mean(losses),
                   "consensus_x": consensus_error(x),
                   "wire_bytes": jnp.asarray(wire, jnp.float32)}
    return DsgdState(x=x, step=state.step + 1), metrics


# ---------------------------------------------------------------------------
# CHOCO-SGD
# ---------------------------------------------------------------------------

class ChocoState(NamedTuple):
    x: Any
    q: Any      # own surrogate x-hat
    m: Any      # mixing mirror: sum_j w_ij x-hat_j
    step: jax.Array


def choco_init(params, n_agents: int, plane_dtype=None) -> ChocoState:
    """``plane_dtype``: storage dtype of the surrogate/mirror buffers
    (bf16 halves them); the params ``x`` keep their own dtype."""
    x = _stack(params, n_agents)
    dt = jnp.float32 if plane_dtype is None else jnp.dtype(plane_dtype)
    zeros = _tree(lambda l: jnp.zeros_like(l, dtype=dt), x)
    return ChocoState(x=x, q=zeros, m=zeros, step=jnp.zeros((), jnp.int32))


def choco_step(eta: float, gamma: float, loss_fn: LossFn,
               mixer: Optional[MixFn], compressor: Optional[Compressor],
               state: ChocoState, batch, key,
               tau: Optional[float] = None, clip_mode: str = "smooth",
               engine: Optional[CommRound] = None,
               ) -> Tuple[ChocoState, Dict[str, jax.Array]]:
    """CHOCO-SGD: x+ = x - eta g;  q += C(x+ - q);  x = x+ + gamma (m - q)."""
    eng = resolve_engine(engine, mixer, compressor)
    n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    k_g, k_c = jax.random.split(key)
    keys = jax.random.split(k_g, n)

    def agent_grad(p, b, k):
        del k
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if tau is not None:
            g = clipping.tree_clip(g, tau, clip_mode)
        return loss, g

    with jax.named_scope("oracle"):
        losses, g = jax.vmap(agent_grad)(state.x, batch, keys)
    x_half = _tree(lambda x0, gg: x0 - eta * gg, state.x, g)
    x, q, m = eng.gossip_apply(k_c, x_half, state.q, state.m, gamma,
                               t=state.step)
    with jax.named_scope("step.metrics"):
        metrics = {"loss": jnp.mean(losses),
                   "consensus_x": consensus_error(x),
                   "wire_bytes": jnp.asarray(eng.wire_bytes(state.x),
                                             jnp.float32)}
    return ChocoState(x=x, q=q, m=m, step=state.step + 1), metrics


# ---------------------------------------------------------------------------
# Centralized DP-SGD (Table 1 baseline)
# ---------------------------------------------------------------------------

class DpSgdState(NamedTuple):
    x: Any
    step: jax.Array


def dpsgd_init(params) -> DpSgdState:
    # copy: the state must own its buffers -- the chunked runtime donates
    # them, which would otherwise delete the caller's params mid-harness
    return DpSgdState(x=_tree(jnp.array, params),
                      step=jnp.zeros((), jnp.int32))


def dpsgd_step(eta: float, loss_fn: LossFn, state: DpSgdState, batch, key,
               tau: float = 1.0, clip_mode: str = "smooth",
               sigma_p: float = 0.0) -> Tuple[DpSgdState, Dict[str, jax.Array]]:
    with jax.named_scope("oracle"):
        loss, g = _dp_gradient(loss_fn, state.x, batch, key, tau, clip_mode,
                               sigma_p)
    x = _tree(lambda x0, gg: x0 - eta * gg, state.x, g)
    # one dense gradient upload to the server per round, at each buffer's
    # actual dtype width (a bf16 run moves half the bytes of an f32 one)
    wire = sum(int(l.size) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(state.x))
    return DpSgdState(x=x, step=state.step + 1), {
        "loss": loss, "wire_bytes": jnp.asarray(float(wire), jnp.float32)}


# ---------------------------------------------------------------------------
# SoteriaFL-SGD (server/client, shifted compression)
# ---------------------------------------------------------------------------

class SoteriaState(NamedTuple):
    x: Any       # server model (replicated view)
    h: Any       # per-client shift, agent-stacked
    h_bar: Any   # server-side average shift
    step: jax.Array


def soteria_init(params, n_agents: int, plane_dtype=None) -> SoteriaState:
    """``plane_dtype``: storage dtype of the agent-stacked client shifts
    ``h`` (the memory-dominant buffer; bf16 halves it).  The server-side
    ``h_bar`` is a single replica and stays f32 exact."""
    dt = jnp.float32 if plane_dtype is None else jnp.dtype(plane_dtype)
    zeros_stacked = _tree(
        lambda p: jnp.zeros((n_agents,) + p.shape, dt), params)
    zeros = _tree(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    # copy x: the state must own its buffers (donation-safe, see dpsgd_init)
    return SoteriaState(x=_tree(jnp.array, params), h=zeros_stacked,
                        h_bar=zeros, step=jnp.zeros((), jnp.int32))


def soteria_step(eta: float, alpha_shift: float, loss_fn: LossFn,
                 compressor: Optional[Compressor], state: SoteriaState,
                 batch, key,
                 tau: float = 1.0, clip_mode: str = "smooth",
                 sigma_p: float = 0.0,
                 engine: Optional[CommRound] = None
                 ) -> Tuple[SoteriaState, Dict[str, jax.Array]]:
    """SoteriaFL-SGD: clients send C(g_i - h_i); server uses h_bar + mean(c).

    g_i is the per-sample-clipped + perturbed local gradient (LDP).  The
    client side is the engine's shifted-compression primitive; the server
    mean replaces the gossip mirror.
    """
    eng = resolve_engine(engine, None, compressor)
    n = jax.tree_util.tree_leaves(state.h)[0].shape[0]
    k_g, k_c = jax.random.split(key)
    keys = jax.random.split(k_g, n)

    def client(h_i, b, k):
        loss, g = _dp_gradient(loss_fn, state.x, b, k, tau, clip_mode, sigma_p)
        return loss, g

    with jax.named_scope("oracle"):
        losses, g = jax.vmap(client)(state.h, batch, keys)
    c, h = eng.shift(k_c, g, state.h, scale=alpha_shift)
    c_bar = _tree(lambda cc: jnp.mean(cc, axis=0), c)
    g_tilde = _tree(jnp.add, state.h_bar, c_bar)
    h_bar = _tree(lambda hb, cb: hb + alpha_shift * cb, state.h_bar, c_bar)
    x = _tree(lambda x0, gt: (x0 - eta * gt).astype(x0.dtype), state.x, g_tilde)
    # n compressed client uploads per round (server broadcast not counted,
    # matching the LDP literature's upload accounting); accounted from the
    # engine so the metric always reflects the compressor that actually ran
    wire = eng.wire_bytes(state.h)
    with jax.named_scope("step.metrics"):
        metrics = {"loss": jnp.mean(losses),
                   "wire_bytes": jnp.asarray(wire, jnp.float32)}
    return SoteriaState(x=x, h=h, h_bar=h_bar, step=state.step + 1), metrics
