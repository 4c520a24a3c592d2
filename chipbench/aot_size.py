#!/usr/bin/env python3
"""Device memory and kernel count of each cell's chunk program, compiled
for a described TPU v5e without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/aot_size.py [--workload NAME ...]

For each cell of BENCHMARK.json, the chunk program that the window drives
(``make_runner`` over the registry algorithm's step, with the compiled
Pallas kernels) is lowered for one chip of a described ``v5e:2x2``, or for
all four with the cell's shardings, and compiled by the TPU compiler
installed here.  ``build_train_step`` offers no choice of interpret mode,
so for four-chip cells the facade's spec is built with ``interpret=False``
here (on a CPU host the kernels would otherwise stay interpreted).  It prints
``memory_analysis()`` (arguments + outputs - aliased + temporaries, against
the chip's 15.75 GiB) and the number of ``tpu_custom_call`` ops.  Nothing
runs; the persistent compile cache is off, since a compile for a described
chip cannot be read back without one.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path
from typing import Optional
from unittest import mock

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
GIB = 2 ** 30


def size_cell(workload: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from chipbench import cell as C

    entry, _, config, traffic, _ = C.load_cell(workload)
    if entry["chips"] == 1:
        cell = C.build(config, traffic, comm_backend="pallas",
                       interpret=False)
        one = SingleDeviceSharding(topo.devices[0])
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            jax.eval_shape(cell.init, jax.random.PRNGKey(0)))
        repl = one
    else:
        from repro import api

        @dataclasses.dataclass(frozen=True)
        class CompiledKernels(api.ExperimentSpec):
            interpret: Optional[bool] = False

        with mock.patch.object(api, "ExperimentSpec", CompiledKernels):
            cell = C.build(config, traffic, comm_backend="pallas",
                           devices=topo.devices)
        state = jax.eval_shape(cell.init, jax.random.PRNGKey(0))
        mesh = jax.tree_util.tree_leaves(state)[0].sharding.mesh
        repl = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
    compiled = cell.runner.jitted.lower(state, key, start).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return {"workload": workload, "gib": total / GIB,
            "chips": entry["chips"],
            "arguments_gib": mem.argument_size_in_bytes / GIB,
            "temporaries_gib": mem.temp_size_in_bytes / GIB,
            "tpu_custom_call": compiled.as_text().count("tpu_custom_call")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="*", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    from jax.experimental import topologies

    from chipbench import cell as C

    jax.config.update("jax_enable_compilation_cache", False)
    bench = C.load_json(ROOT / "BENCHMARK.json")
    names = args.workload or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        r = size_cell(name, topo)
        print(f"{r['workload']}: {r['gib']:.2f} GiB of 15.75 per chip, "
              f"{r['chips']} chip(s) (arguments "
              f"{r['arguments_gib']:.2f}, temporaries "
              f"{r['temporaries_gib']:.2f}), {r['tpu_custom_call']} "
              "tpu_custom_call", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
