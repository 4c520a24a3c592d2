"""Process-environment knobs that must be set before jax backend init.

jax locks the host device count at first backend initialization, so any
driver that wants forced host devices (dry-run sweeps, sharded CPU
benchmarks) has to mutate XLA_FLAGS before anything queries a device.
This module is deliberately jax-free (and importable through the
docstring-only ``repro`` package root) so callers can import it first,
then import jax.

One shared implementation instead of a copy per driver: the append/defer
precedence rule lives here only.  The persistent compile cache is placed
here too (:func:`use_compile_cache`), for the same reason: jax reads its
location from the environment when it is imported.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

__all__ = ["ensure_host_device_count", "use_compile_cache"]

_FLAG = "--xla_force_host_platform_device_count"
_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


def ensure_host_device_count(n: int) -> None:
    """Force ``n`` host platform devices unless the caller already chose.

    Appends to any user-provided XLA_FLAGS (never clobbers them) and
    defers entirely when a host-device count is already present -- running
    a driver under an outer harness that set its own count keeps the outer
    choice.  A no-op after jax backend init (the count is locked); call
    before importing anything that might initialize jax.
    """
    existing = os.environ.get("XLA_FLAGS", "")
    if _FLAG in existing:
        return
    os.environ["XLA_FLAGS"] = f"{existing} {_FLAG}={n}".strip()


def use_compile_cache() -> str:
    """Turn on jax's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax uses it and nothing
    else is set here.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout: a fixed path, so that a later run finds what an
    earlier one wrote.  Entry points call this first thing in their ``__main__``; tests
    never do, so they run without a cache.  When jax is already imported
    (``python -m`` imports the package first), its flag is updated too --
    through ``sys.modules``, so this module still never imports jax.
    """
    path = os.environ.get(_CACHE_VAR)
    if path:
        return path
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    os.environ[_CACHE_VAR] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
