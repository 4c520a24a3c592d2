"""Gradient clipping operators (paper Definition 2 and Remark 1).

* ``smooth_clip``     Clip_tau(x) = tau / (tau + ||x||) * x      (Definition 2)
* ``piecewise_clip``  Clip_tau(x) = x * min(1, tau/||x||)        (Remark 1)

Both map any vector into the ball of radius tau; the smooth variant is a
strict contraction (||Clip(x)|| < tau always) which is what the paper's
analysis uses, and what Theorem 1's sensitivity bound relies on.

Pytree versions clip by the *global* norm across all leaves (the model
parameter vector x in the paper is the flattened pytree).  Per-sample
clipped mini-batch gradients for PORTER-DP are produced by
``clipped_grad_accumulate`` which scans over the local batch so the
activation working set stays one-sample-sized (TPU memory-hierarchy
adaptation of DP-SGD, see DESIGN.md).
"""

from __future__ import annotations

import functools
from typing import Callable, Literal

import jax
import jax.numpy as jnp

__all__ = [
    "smooth_clip",
    "piecewise_clip",
    "tree_global_norm",
    "tree_clip",
    "clip_factor",
    "clipped_grad_accumulate",
]

ClipMode = Literal["smooth", "piecewise", "none"]


def smooth_clip(x: jax.Array, tau: float) -> jax.Array:
    """Definition 2 on a single array (norm over the whole array)."""
    nrm = jnp.linalg.norm(x.reshape(-1))
    return (tau / (tau + nrm)) * x


def piecewise_clip(x: jax.Array, tau: float) -> jax.Array:
    """Remark 1 on a single array."""
    nrm = jnp.linalg.norm(x.reshape(-1))
    return x * jnp.minimum(1.0, tau / jnp.maximum(nrm, 1e-30))


def tree_global_norm(tree) -> jax.Array:
    """l2 norm of the concatenation of all leaves (per the paper's x in R^d)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_factor(norm: jax.Array, tau: float, mode: ClipMode) -> jax.Array:
    if mode == "smooth":
        return tau / (tau + norm)
    if mode == "piecewise":
        return jnp.minimum(1.0, tau / jnp.maximum(norm, 1e-30))
    if mode == "none":
        return jnp.ones_like(norm)
    raise ValueError(f"unknown clip mode {mode!r}")


def tree_clip(tree, tau: float, mode: ClipMode = "smooth"):
    """Clip a pytree by its global l2 norm."""
    with jax.named_scope("oracle.clip"):
        norm = tree_global_norm(tree)
        c = clip_factor(norm, tau, mode)
        return jax.tree_util.tree_map(lambda l: (l * c).astype(l.dtype),
                                      tree)


def clipped_grad_accumulate(
    loss_fn: Callable,
    params,
    batch,
    tau: float,
    mode: ClipMode = "smooth",
) -> tuple:
    """Mean of per-sample clipped gradients: (1/b) sum_z Clip_tau(grad l(x; z)).

    This is PORTER-DP line 6.  ``batch`` is a pytree whose leaves have a
    leading local-batch axis b; the scan peels one sample at a time so peak
    memory is one sample's activations plus one parameter-sized accumulator.

    Returns (mean_clipped_grad, mean_loss).
    """
    b = jax.tree_util.tree_leaves(batch)[0].shape[0]
    grad_fn = jax.value_and_grad(loss_fn)

    def body(carry, idx):
        acc, loss_acc = carry
        # keep a singleton batch dim: loss_fns are written for batched inputs
        sample = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=0), batch)
        loss, g = grad_fn(params, sample)
        g = tree_clip(g, tau, mode)
        with jax.named_scope("oracle.clip"):
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
        return (acc, loss_acc + loss), None

    zeros = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                                   params)
    (acc, loss_sum), _ = jax.lax.scan(body, (zeros, 0.0), jnp.arange(b))
    with jax.named_scope("oracle.clip"):
        mean_g = jax.tree_util.tree_map(lambda a: a / b, acc)
    return mean_g, loss_sum / b
