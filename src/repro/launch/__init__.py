"""Launcher: production meshes, input specs, sharded step builders, dry-run.

NOTE: repro.launch.dryrun must be imported/run FIRST in its process (it sets
XLA_FLAGS before jax initializes); do not import it from here.
"""
from . import mesh, runtime, shapes, steps
from .mesh import (HW, agent_axes, make_host_mesh, make_mesh,
                   make_production_mesh, n_agents)
from .runtime import BatchSource, make_runner, run_chunked

__all__ = ["mesh", "shapes", "steps", "runtime", "make_mesh",
           "make_host_mesh", "make_production_mesh",
           "agent_axes", "n_agents", "HW", "BatchSource", "make_runner",
           "run_chunked"]
