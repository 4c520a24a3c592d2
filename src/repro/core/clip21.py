"""Clip21-style error-feedback clipping (arXiv 2305.18929), decentralized.

Plain clipping biases the update whenever gradients exceed tau -- the
clipped-off mass is simply lost, and PORTER's Theorems pay for it with a
neighbourhood term.  Clip21 removes the bias *asymptotically* by clipping
the **residual** against a per-agent running estimate instead of the
gradient itself (EF21 with Clip in place of the compressor):

    delta_i^t = g_i^t - hat g_i^{t-1}
    hat g_i^t = hat g_i^{t-1} + Clip_tau(delta_i^t)

Once the iterates stabilize, ||delta|| falls below tau and the estimate
tracks the true gradient *exactly* -- each application contracts the
residual by at least tau in norm (:func:`clip21_update`; the hypothesis
suite pins both contraction inequalities).

Decentralized composition: ``hat g^t`` simply replaces the gradient oracle
of PORTER's Algorithm 1 -- the tracking/consensus comm rounds (lines
11-14) are untouched, making this a thin CommRound client.  The step
re-runs porter's *unclipped* gradient oracle with the identical key
schedule and hands ``(losses, hat g)`` to :func:`repro.core.porter
.porter_step` via ``grad_override``; with tau = inf the clip factor is
exactly 1.0, ``hat g = g`` bitwise, and the whole step is **bit-exact**
against porter-gc with a piecewise clip at tau = inf (pinned by
tests/test_fleet.py).

Clipping is piecewise (min(1, tau/||delta||), paper Remark 1): the smooth
surrogate tau/(tau+||delta||) never reaches factor 1, so the EF estimate
would never lock on (and tau = inf would be 0*inf = NaN).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import clipping
from .comm_round import CommRound
from .compression import Compressor
from .gossip import MixFn
from .porter import (PorterConfig, PorterState, _agent_gradient, porter_init,
                     porter_step)

__all__ = [
    "Clip21State",
    "clip21_update",
    "clip21_init",
    "clip21_step",
]


class Clip21State(NamedTuple):
    base: PorterState   # porter's x/v/EF planes, incl. the round counter
    g_est: Any          # hat g: per-agent EF gradient estimate


def clip21_update(g_est: Any, g_raw: Any, tau: float) -> Any:
    """One agent's EF-clip: ``g_est + Clip_tau(g_raw - g_est)``.

    Piecewise factor f = min(1, tau/||delta||).  Written as a ``where`` on
    f >= 1 rather than ``g_est + f*delta`` so the locked-on branch returns
    ``g_raw`` *bitwise* (a + 1.0*(b - a) only approximates b in floats);
    tau = inf therefore reduces to the identity on the raw gradient.

    Contraction (the Clip21 descent ingredient, pinned by hypothesis):
    the new residual r' = g_raw - g_est' satisfies both
    ``||r'|| <= ||r||`` and ``||r'|| <= max(||r|| - tau, 0)``.
    """
    with jax.named_scope("oracle.clip"):
        delta = jax.tree_util.tree_map(lambda a, b: a - b, g_raw, g_est)
        factor = clipping.clip_factor(clipping.tree_global_norm(delta), tau,
                                      "piecewise")
        return jax.tree_util.tree_map(
            lambda ge, gr, d: jnp.where(factor >= 1.0, gr,
                                        (ge + factor * d).astype(gr.dtype)),
            g_est, g_raw, delta)


def clip21_init(params: Any, n_agents: int, w=None,
                buffer_dtype: Any = jnp.float32,
                plane_dtype: Any = None) -> Clip21State:
    """hat g^0 = 0: the first round clips the full gradient (as in the
    paper), and porter's own planes initialize exactly as porter-gc's."""
    base = porter_init(params, n_agents, w=w, buffer_dtype=buffer_dtype,
                       plane_dtype=plane_dtype)
    g_est = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), base.x)
    return Clip21State(base=base, g_est=g_est)


def clip21_step(
    cfg: PorterConfig,
    loss_fn,
    mixer: Optional[MixFn],
    compressor: Optional[Compressor],
    state: Clip21State,
    batch: Any,
    key: jax.Array,
    compress_fn=None,
    engine: Optional[CommRound] = None,
) -> Tuple[Clip21State, Dict[str, jax.Array]]:
    """One Clip21 iteration: EF-clipped oracle + porter comm rounds.

    ``cfg.tau`` is the residual clip threshold; the raw gradient is never
    clipped (variant forced to 'beer' for the oracle call).  The key is
    split exactly as porter_step splits it, so the gradient batch noise
    and both comm-round streams coincide with porter-gc's.
    """
    n = jax.tree_util.tree_leaves(state.base.x)[0].shape[0]
    _, k_noise, _, _ = jax.random.split(key, 4)
    agent_keys = jax.random.split(k_noise, n)
    raw_cfg = dataclasses.replace(cfg, variant="beer")
    grad_fn = functools.partial(_agent_gradient, raw_cfg, loss_fn)
    with jax.named_scope("oracle"):
        losses, g_raw = jax.vmap(grad_fn)(state.base.x, batch, agent_keys)
        g_est = jax.vmap(lambda ge, gr: clip21_update(ge, gr, cfg.tau))(
            state.g_est, g_raw)

    base, metrics = porter_step(cfg, loss_fn, mixer, compressor, state.base, batch,
                                key, compress_fn=compress_fn, engine=engine,
                                grad_override=(losses, g_est))
    with jax.named_scope("step.metrics"):
        resid = jax.tree_util.tree_map(lambda a, b: a - b, g_raw, g_est)
        metrics["clip_residual"] = (clipping.tree_global_norm(resid)
                                    / jnp.sqrt(jnp.float32(n)))
    return Clip21State(base=base, g_est=g_est), metrics
