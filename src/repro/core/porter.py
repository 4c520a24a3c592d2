"""PORTER (paper Algorithm 1): decentralized nonconvex optimization with
gradient clipping and communication compression.

State layout: every buffer is an *agent-stacked pytree* -- each leaf carries a
leading ``n_agents`` axis which, under pjit, is sharded over the mesh's agent
axes (``('data',)`` or ``('pod','data')``).  Buffers (paper notation):

    x       X^t      parameters, one replica per agent
    v       V^t      gradient-tracking estimates
    q_x     Q_x^t    compressed surrogate of X (error feedback)
    q_v     Q_v^t    compressed surrogate of V
    g_prev  G_p^t    previous perturbed/clipped stochastic gradient
    m_x     (W Q_x)  mixing mirror: sum_j w_ij q_{x,j}, accumulated from wire
    m_v     (W Q_v)  increments -- see core/gossip.py; (Q(W-I))_i = m_i - q_i

The two mirrors are the receive-side state a real deployment keeps anyway;
they let every wire format (dense / ring / packed top-k) share one algorithm
body.

One iteration (Algorithm 1, lines 4-14):

    G^t   = clipped/perturbed stochastic gradient at X^{t-1}     (DP or GC)
    c_v   = C(V^{t-1} - Q_v^{t-1});  Q_v += c_v;  M_v += W c_v   (comm)
    V^t   = V^{t-1} + gamma (M_v - Q_v) + G^t - G^{t-1}
    c_x   = C(X^{t-1} - Q_x^{t-1});  Q_x += c_x;  M_x += W c_x   (comm)
    X^t   = X^{t-1} + gamma (M_x - Q_x) - eta V^t

The communication + fused-update halves (lines 11-14) are delegated to the
comm-round engine (:class:`repro.core.comm_round.CommRound`): ``track`` is
lines 11-12, ``step`` is lines 13-14.  This module only owns the gradient
oracle (lines 4-10) and the metrics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import clipping
from .comm_round import CommRound, compress_stacked, resolve_engine
from .compression import Compressor
from .gossip import MixFn, make_dense_mixer
from .mixing import Topology

__all__ = [
    "PorterConfig",
    "PorterState",
    "porter_init",
    "porter_step",
    "make_porter_step",
    "average_params",
    "consensus_error",
]

LossFn = Callable[[Any, Any], jax.Array]  # (params, batch) -> scalar loss

# Backwards-compatible alias: the per-agent compression helper now lives in
# comm_round (it is the engine's default compress path).
_compress_stacked = compress_stacked


@dataclasses.dataclass(frozen=True)
class PorterConfig:
    """Hyper-parameters of Algorithm 1.

    variant: 'dp' (clip-then-batch + Gaussian noise, Option I),
             'gc' (batch-then-clip, Option II),
             'beer' (no clipping -- the BEER ancestor, tau ignored).
    """

    eta: float                      # gradient stepsize
    gamma: float                    # consensus stepsize
    tau: float = 1.0                # clipping threshold
    variant: str = "gc"             # 'dp' | 'gc' | 'beer'
    clip_mode: str = "smooth"       # 'smooth' | 'piecewise'
    sigma_p: float = 0.0            # DP perturbation std (Theorem 1)
    grad_dtype: Any = jnp.float32   # accumulation dtype for the EF buffers

    def __post_init__(self):
        if self.variant not in ("dp", "gc", "beer"):
            raise ValueError(f"unknown variant {self.variant!r}")


class PorterState(NamedTuple):
    x: Any
    v: Any
    q_x: Any
    q_v: Any
    g_prev: Any
    m_x: Any
    m_v: Any
    step: jax.Array


def _zeros_like_f(tree, dtype):
    return jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, dtype), tree)


def porter_init(params: Any, n_agents: int, w: Optional[np.ndarray] = None,
                buffer_dtype: Any = jnp.float32,
                plane_dtype: Any = None) -> PorterState:
    """Initialize from a single replica; X^0 = x0 1^T (paper line 2).

    ``plane_dtype``: storage dtype for the six EF buffers (q_x, q_v, m_x,
    m_v, v, g_prev) -- ``'bf16'``/``jnp.bfloat16`` halves the resident
    optimizer state while the master params ``x`` keep their own dtype
    (typically f32) for an exact parameter trajectory.  None keeps the
    legacy layout: surrogates in x's dtype, zeros in ``buffer_dtype``.
    """
    x = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (n_agents,) + p.shape), params)
    pdt = None if plane_dtype is None else jnp.dtype(plane_dtype)
    zeros = _zeros_like_f(x, buffer_dtype if pdt is None else pdt)
    if w is None:
        m_x = x  # all agents equal and rows of W sum to 1 => W X0 = X0
    else:
        mixer = make_dense_mixer(w)
        m_x = mixer(x)
    q_x = x
    if pdt is not None:
        q_x = jax.tree_util.tree_map(lambda l: l.astype(pdt), x)
        m_x = jax.tree_util.tree_map(lambda l: l.astype(pdt), m_x)
    return PorterState(x=x, v=zeros, q_x=q_x, q_v=zeros, g_prev=zeros,
                       m_x=m_x, m_v=zeros, step=jnp.zeros((), jnp.int32))


def perturb(g, key: jax.Array, sigma_p: float):
    """The Gaussian perturbation of a DP gradient: each leaf plus
    ``sigma_p`` times its own standard normal draw."""
    with jax.named_scope("oracle.noise"):
        leaves, treedef = jax.tree_util.tree_flatten(g)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            l + sigma_p * jax.random.normal(k, l.shape, l.dtype)
            for k, l in zip(keys, leaves)])


def _agent_gradient(cfg: PorterConfig, loss_fn: LossFn, params, batch,
                    key: jax.Array) -> Tuple[jax.Array, Any]:
    """One agent's G_p (Algorithm 1 lines 5-10).  batch leaves: (b, ...)."""
    if cfg.variant == "dp":
        # Option I: clip each sample's gradient, average, perturb.
        g, loss = clipping.clipped_grad_accumulate(
            loss_fn, params, batch, cfg.tau, cfg.clip_mode)
        return loss, perturb(g, key, cfg.sigma_p)
    # Option II / BEER: one batch gradient, clip after (or not at all).
    loss, g = jax.value_and_grad(loss_fn)(params, batch)
    if cfg.variant == "gc":
        g = clipping.tree_clip(g, cfg.tau, cfg.clip_mode)
    return loss, g


# Backwards-compatible alias: engine resolution (and its conflict check)
# lives in comm_round; porter_adam and older call sites import it from here.
_resolve_engine = resolve_engine


def porter_step(
    cfg: PorterConfig,
    loss_fn: LossFn,
    mixer: Optional[MixFn],
    compressor: Optional[Compressor],
    state: PorterState,
    batch: Any,
    key: jax.Array,
    compress_fn=None,
    engine: Optional[CommRound] = None,
    grad_override: Optional[Tuple[jax.Array, Any]] = None,
) -> Tuple[PorterState, Dict[str, jax.Array]]:
    """One PORTER iteration over all agents (pure; jit/pjit-able).

    batch: pytree with leaves (n_agents, b, ...).
    compress_fn: optional (key, tree) -> tree override for the compression
    (e.g. the shard-local compressor from repro.launch.steps, which keeps
    top-k selection inside each model shard and avoids resharding
    all-gathers).  Defaults to per-agent-row compression of ``compressor``.
    engine: optional pre-built CommRound (the facade repro.api.build makes
    one per algorithm).  An engine owns its compressor/mixer/compress_fn;
    passing a *different* object alongside ``engine=`` raises (it used to be
    silently ignored).  With ``engine=`` the positional mixer/compressor may
    simply be None.
    grad_override: optional ``(losses, g)`` replacing the gradient oracle
    (lines 4-10) while keeping the comm rounds (lines 11-14) -- clip21
    feeds its error-feedback clipped gradient through here.  ``losses`` is
    the per-agent loss vector, ``g`` the agent-stacked gradient tree; the
    key is still consumed identically so PRNG streams stay aligned with
    the un-overridden step.
    """
    eng = resolve_engine(engine, mixer, compressor, compress_fn)
    n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    _, k_noise, k_cv, k_cx = jax.random.split(key, 4)

    # ---- stochastic gradients (local; lines 4-10) -------------------------
    with jax.named_scope("oracle"):
        if grad_override is None:
            agent_keys = jax.random.split(k_noise, n)
            grad_fn = functools.partial(_agent_gradient, cfg, loss_fn)
            losses, g = jax.vmap(grad_fn)(state.x, batch, agent_keys)
        else:
            losses, g = grad_override
        g = jax.tree_util.tree_map(lambda l: l.astype(cfg.grad_dtype), g)

    # ---- comm rounds: track (lines 11-12) + step (lines 13-14) ------------
    # the state's own step counter is the absolute round index: it advances
    # inside the scan, survives checkpoints, and selects W_t when the mixer
    # runs a time-varying topology schedule (static mixers ignore it)
    if eng.overlap:
        # comm/compute overlap: the x-side exchange reads only (x, q_x),
        # which the v-side update never touches, so both compress+collective
        # pairs are issued before either fused update -- the collectives
        # run while the other round's local compute proceeds, and every
        # value equals the sequential order's (bit-exact by construction)
        # SR keys split exactly as the sequential track/step would, so
        # overlap stays bit-exact under mixed precision too
        k_cv, sr_v = eng.sr_split(k_cv, (state.q_v, state.m_v, state.v))
        k_cx, sr_x = eng.sr_split(k_cx, (state.q_x, state.m_x, state.x))
        c_v, wc_v = eng.exchange(k_cv, state.v, state.q_v, t=state.step)
        c_x, wc_x = eng.exchange(k_cx, state.x, state.q_x, t=state.step)
        v, q_v, m_v = eng.track_update(c_v, wc_v, state.v, state.q_v,
                                       state.m_v, g, state.g_prev, cfg.gamma,
                                       sr_key=sr_v)
        x, q_x, m_x = eng.step_update(c_x, wc_x, state.x, state.q_x,
                                      state.m_x, v, cfg.gamma, cfg.eta,
                                      sr_key=sr_x)
    else:
        v, q_v, m_v = eng.track(k_cv, state.v, state.q_v, state.m_v, g,
                                state.g_prev, cfg.gamma, t=state.step)
        x, q_x, m_x = eng.step(k_cx, state.x, state.q_x, state.m_x, v,
                               cfg.gamma, cfg.eta, t=state.step)

    new_state = PorterState(x=x, v=v, q_x=q_x, q_v=q_v, g_prev=g,
                            m_x=m_x, m_v=m_v, step=state.step + 1)
    with jax.named_scope("step.metrics"):
        metrics = {
            "loss": jnp.mean(losses),
            "consensus_x": consensus_error(x),
            "consensus_v": consensus_error(v),
            "v_norm": clipping.tree_global_norm(v) / np.sqrt(n),
            # two compressed streams (Q_x and Q_v) per round
            "wire_bytes": jnp.asarray(2.0 * eng.wire_bytes(state.x),
                                      jnp.float32),
        }
    return new_state, metrics


def make_porter_step(cfg: PorterConfig, loss_fn: LossFn, mixer: MixFn,
                     compressor: Compressor, compress_fn=None,
                     backend: str = "auto",
                     interpret: Optional[bool] = None):
    """Bind the static pieces; returns step(state, batch, key).

    backend / interpret configure the comm-round engine ('auto' = fused
    Pallas kernels on TPU, jnp reference elsewhere).
    """
    engine = CommRound(compressor=compressor, mixer=mixer,
                       compress_fn=compress_fn, backend=backend,
                       interpret=interpret)
    return functools.partial(porter_step, cfg, loss_fn, None, None,
                             engine=engine)


def average_params(x_stacked):
    """x-bar: the average replica (paper's evaluation point)."""
    return jax.tree_util.tree_map(lambda l: jnp.mean(l, axis=0), x_stacked)


def consensus_error(tree) -> jax.Array:
    """|| Y - y_bar 1^T ||_F^2 across all leaves."""
    def leaf_err(l):
        lf = l.astype(jnp.float32)
        mean = jnp.mean(lf, axis=0, keepdims=True)
        return jnp.sum(jnp.square(lf - mean))

    return sum(leaf_err(l) for l in jax.tree_util.tree_leaves(tree))
