"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (jax locks the device count on first backend init, and the
smoke tests must see 1 CPU device while the dry-run sees 512 forced hosts).

Single pod : (data=16, model=16)            = 256 chips (v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips
One host   : (data=n, model=1)              = n chips, one agent per chip

PORTER's decentralized agents live on the *agent axes*: ('data',) single-pod
(16 agents), ('pod','data') multi-pod (32 agents).  Tensor parallelism for
each agent's replica lives on 'model'.

Every mesh in the repo is built by :func:`make_mesh`, which names its axes
``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
``vmap`` over an agent-sharded leaf is refused, and the sharded code here
relies on the partitioner propagating shardings.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "agent_axes", "n_agents", "HW"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_agents: int, devices=None):
    """One agent per chip on ``'data'``; ``'model'`` has size 1, so the
    model's tensor-parallel specs resolve and every leaf stays whole on
    its agent's chip."""
    return make_mesh((n_agents, 1), ("data", "model"), devices=devices)


def agent_axes(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_agents(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in agent_axes(mesh)]))


class HW:
    """TPU v5e hardware constants for the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link
    HBM_BYTES = 16 * 2**30          # 16 GiB per chip
