#!/usr/bin/env python3
"""Upper readings of a cell's limits: the control and the planted faults.

    python3 chipbench/control.py --workload minicpm3-gc --seeds 11 12 13

For each seed, the plain reference in float32 is compared, by the numbers
that decide ``correct``, with

* ``control``: the same reference with its matmuls in float8 (e4m3), the
  precision below the configuration's bfloat16;
* ``half_batch``: the reference with half of each agent's batch left out,
  the mean taken over the rest;
* ``no_exchange``: the reference with the gossip mix left out (W = I);

and a state left unchanged reads 1 on ``dx`` by construction.  With
``--program-seeds`` it also reads the lower readings in the same process:
for each of those seeds the program's first chunk, built and driven as a
run's set-up drives it, against the reference.

    python3 chipbench/control.py --workload minicpm3-gc --seeds 11 12 13 \
        --program-seeds 11 12 13 14 15 16 17 18 19 20 21 22

The benchmark's own runs never run this; it reads the limits' readings on
the chip at the cell's own size, and ``test_bench_check_*.py`` run it on
micro cells that a test run holds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def readings(workload: str, seeds, bench_path=ROOT / "BENCHMARK.json",
             variants=("control", "half_batch", "no_exchange")) -> list:
    """[{seed, variant: {number: value}}] for each seed."""
    import jax

    from chipbench import cell as C
    from chipbench.reference import Reference, compare

    entry, _, config, traffic, _ = C.load_cell(workload, bench_path)
    cfg = C.model_config(config)
    from repro.models import build_model
    shapes = jax.eval_shape(lambda k: build_model(cfg).init(k)[0],
                            jax.random.PRNGKey(0))
    refs = {"control": Reference(config, traffic, shapes, precision="fp8"),
            "half_batch": Reference(config, traffic, shapes,
                                    fault="half_batch"),
            "no_exchange": Reference(config, traffic, shapes,
                                     fault="no_exchange")}
    base = Reference(config, traffic, shapes)
    out = []
    with jax.default_matmul_precision("highest"):
        for seed in seeds:
            r_losses, r_norms, _ = base.run(seed, traffic["chunk"])
            row = {"seed": seed}
            for v in variants:
                losses, norms_, _ = refs[v].run(seed, traffic["chunk"])
                row[v] = compare(losses, norms_, r_losses, r_norms)
            out.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    return out


def program_readings(workload: str, seeds,
                     bench_path=ROOT / "BENCHMARK.json") -> list:
    """[{seed, program: {number: value}}]: the program's first chunk from
    each seed, through the cell's own runner, against the reference."""
    import jax
    import numpy as np

    from chipbench import cell as C
    from chipbench.reference import Reference, compare
    from chipbench.run import state_norms

    _, _, config, traffic, _ = C.load_cell(workload, bench_path)
    cell = C.build(config, traffic)
    base = Reference(config, traffic, cell.param_shapes)
    out = []
    for seed in seeds:
        kw, kr = C.stream_keys(seed)
        if cell.key_sharding is not None:
            kr = jax.device_put(kr, cell.key_sharding)
        state, _, m = cell.runner(cell.init(kw), kr, 0)
        losses = np.asarray(m["loss"], np.float64)
        norms = state_norms(cell, state, kw)
        del state, m
        with jax.default_matmul_precision("highest"):
            r_losses, r_norms, _ = base.run(seed, traffic["chunk"])
        row = {"seed": seed,
               "program": compare(losses, norms, r_losses, r_norms)}
        out.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    from chipbench.run import check_devices, use_cache
    use_cache(jax)
    check_devices(jax, 1)
    rows = (program_readings(args.workload, args.program_seeds)
            if args.program_seeds else [])
    rows += readings(args.workload, args.seeds)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
