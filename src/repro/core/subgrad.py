"""Nonsmooth decentralized subgradient method with compressed gossip
(arXiv 2607.01755 family).

For nonsmooth objectives (hinge losses, l1 terms, ReLU kinks) the smooth
analysis behind PORTER's gradient tracking does not apply, but the
classical subgradient scheme still converges with a diminishing stepsize;
composed with a Definition-3 rho-compressor on the gossip wire it is a
one-comm-round CommRound client -- structurally CHOCO-SGD's round with
the constant stepsize replaced by the 1/sqrt(t) schedule the nonsmooth
rate needs:

    x_i^{t+1/2} = x_i^t - (eta / sqrt(t+1)) * u_i^t,   u in d f_i(x_i^t)
    q/m/x via engine.gossip_apply (compressed surrogate gossip)

``jax.grad`` at a kink returns one member of the subdifferential (it is a
valid subgradient everywhere for the piecewise-smooth losses here), so the
oracle body is value_and_grad exactly like the baselines.  Optional
``tau`` clips the subgradient -- the bounded-subgradient assumption
enforced rather than assumed.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import clipping
from .comm_round import CommRound, resolve_engine
from .compression import Compressor
from .gossip import MixFn
from .porter import consensus_error

__all__ = [
    "SubgradState",
    "subgrad_init",
    "subgrad_step",
]


class SubgradState(NamedTuple):
    x: Any
    q: Any      # own surrogate x-hat
    m: Any      # mixing mirror: sum_j w_ij x-hat_j
    step: jax.Array


def subgrad_init(params, n_agents: int, plane_dtype=None) -> SubgradState:
    """Same plane layout as CHOCO (the round body is the same engine
    call); ``plane_dtype`` shrinks the surrogate/mirror storage."""
    x = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (n_agents,) + p.shape), params)
    dt = jnp.float32 if plane_dtype is None else jnp.dtype(plane_dtype)
    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros_like(l, dtype=dt), x)
    return SubgradState(x=x, q=zeros, m=zeros,
                        step=jnp.zeros((), jnp.int32))


def subgrad_step(eta: float, gamma: float, loss_fn,
                 mixer: Optional[MixFn], compressor: Optional[Compressor],
                 state: SubgradState, batch, key,
                 tau: Optional[float] = None, clip_mode: str = "piecewise",
                 engine: Optional[CommRound] = None,
                 ) -> Tuple[SubgradState, Dict[str, jax.Array]]:
    """One compressed-gossip subgradient round (diminishing stepsize)."""
    eng = resolve_engine(engine, mixer, compressor)
    n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    k_g, k_c = jax.random.split(key)
    keys = jax.random.split(k_g, n)

    def agent_subgrad(p, b, k):
        del k
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if tau is not None:
            g = clipping.tree_clip(g, tau, clip_mode)
        return loss, g

    with jax.named_scope("oracle"):
        losses, g = jax.vmap(agent_subgrad)(state.x, batch, keys)
    # nonsmooth rate's schedule: eta_t = eta / sqrt(t + 1)
    eta_t = eta * jax.lax.rsqrt(state.step.astype(jnp.float32) + 1.0)
    x_half = jax.tree_util.tree_map(
        lambda x0, gg: x0 - eta_t * gg.astype(x0.dtype), state.x, g)
    x, q, m = eng.gossip_apply(k_c, x_half, state.q, state.m, gamma,
                               t=state.step)
    with jax.named_scope("step.metrics"):
        metrics = {"loss": jnp.mean(losses),
                   "consensus_x": consensus_error(x),
                   "wire_bytes": jnp.asarray(eng.wire_bytes(state.x),
                                             jnp.float32)}
    return SubgradState(x=x, q=q, m=m, step=state.step + 1), metrics
