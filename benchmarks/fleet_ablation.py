"""Fleet ablation: convergence vs. fleet size n (n >> devices).

The per-device harness tops out at one agent per device; the fleet
subsystem (``core/fleet.py``, ``ExperimentSpec(fleet=True)``) simulates
the whole population as one leading vmapped axis, with sparse COO mixing
above ``FLEET_DENSE_GATE``.  This ablation runs the paper's Section-5.1
logreg protocol (a9a-style features, top-5% compression, tau = 1) at
n = 256 / 1024 / 4096 agents on Dirichlet(0.3)-heterogeneous shards and
reports the two axes the per-device harness cannot measure:

* **convergence vs. n**: final loss / consensus and the loss curve per
  rung, with the rung's spectral gap (the exponential graph keeps the
  same family at every n, so the gap shrinks honestly with log n);
* **throughput**: simulated agent-rounds per wall-clock second through
  the scan-fused chunked runtime.

Every rung must compile exactly ONE executable for its chunk runner (the
round offset is traced, so the n sweep costs one compile per shape and
zero retraces inside a rung -- asserted below).  When the process owns
more than one device (e.g. ``--xla_force_host_platform_device_count=8``
in the CI fleet job), the fleet axis is sharded over a 1-D CPU host mesh
and the same single-executable contract must hold.

Rows: ``fleet/<n>,final_loss,...``; artifacts land in
artifacts/bench/fleet_ablation.json plus the checked-in perf-trajectory
baseline BENCH_fleet.json (EXPERIMENTS.md section "Fleet").

    PYTHONPATH=src python benchmarks/fleet_ablation.py            # full
    PYTHONPATH=src python benchmarks/fleet_ablation.py --smoke    # CI
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):  # `python benchmarks/fleet_ablation.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import ExperimentSpec, build
from repro.core import FLEET_DENSE_GATE
from repro.data import a9a_like, dirichlet_source
from repro.launch.mesh import make_mesh
from repro.launch.runtime import make_runner
from benchmarks import common as C

RUNGS = (256, 1024, 4096)
D_FEAT = 123            # a9a dimensionality (Section 5.1)
SHARD = 16              # samples per agent (Dirichlet-resampled)
BATCH = 4
CHUNK = 8
ALPHA_DIR = 0.3         # Dirichlet heterogeneity


def _fleet_spec(n: int, algo: str) -> ExperimentSpec:
    # Section-5.1 knobs on the exponential graph: the one generator that
    # keeps the same family from the dense gate to n = 100k (ER(0.8)
    # would materialize ~0.8 n^2 edges; fleet ER is degree-sampled and
    # changes family at the gate)
    return ExperimentSpec(algo=algo, n_agents=n, topology="exponential",
                          topology_weights="metropolis", compressor="top_k",
                          frac=0.05, eta=0.05, tau=1.0, fleet=True)


def _fleet_shardings(state, batch_shape, n):
    """Shard the leading fleet axis over every device the process owns
    (1-D host mesh); replicate everything else.  No-op on one device."""
    devs = jax.devices()
    if len(devs) < 2 or n % len(devs) != 0:
        return None, None
    mesh = make_mesh((len(devs),), ("fleet",), devices=devs)

    def spec(leaf):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == n:
            return NamedSharding(mesh, P("fleet",
                                         *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())

    state_sh = jax.tree_util.tree_map(spec, state)
    batch_sh = tuple(
        NamedSharding(mesh, P("fleet", *([None] * (len(s) - 1))))
        for s in batch_shape)
    return state_sh, batch_sh


def run_rung(n: int, steps: int, algo_name: str, seed: int = 0):
    x, y = a9a_like(n * SHARD, D_FEAT, seed=seed)
    source = dirichlet_source(np.asarray(x), np.asarray(y), n_agents=n,
                              batch=BATCH, alpha=ALPHA_DIR, seed=seed)
    loss_fn = C.logreg_loss()
    params0 = {"w": np.zeros(D_FEAT, np.float32),
               "b": np.zeros((), np.float32)}

    algo = build(_fleet_spec(n, algo_name), loss_fn)
    state = algo.init(params0)
    state_sh, batch_sh = _fleet_shardings(
        state, ((n, BATCH, D_FEAT), (n, BATCH)), n)
    runner = make_runner(algo, source, CHUNK, state_sharding=state_sh,
                         batch_sharding=batch_sh)

    key = jax.random.PRNGKey(0)
    per_chunk, t = [], 0
    elapsed, timed_rounds = 0.0, 0
    while t + CHUNK <= steps:
        t0 = time.perf_counter()
        state, key, metrics = runner(state, key, t)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        if t > 0:  # skip the compile chunk
            elapsed += dt
            timed_rounds += CHUNK
        t += CHUNK
        per_chunk.append({k: np.asarray(v) for k, v in metrics.items()})
    n_exec = runner.cache_size()
    assert n_exec in (None, 1), (
        f"n={n}: chunk runner compiled {n_exec} executables (expected 1: "
        "the round offset is traced)")

    m = {k: np.concatenate([c[k] for c in per_chunk])
         for k in per_chunk[0]}
    q = max(len(m["loss"]) // 4, 1)
    top = algo.topology
    gap = getattr(top, "spectral_gap", None)
    rec = {
        "n": n,
        "sparse_path": bool(n > FLEET_DENSE_GATE),
        "spectral_gap": None if gap is None else float(gap),
        "gamma": float(algo.gamma),
        "devices": len(jax.devices()),
        "sharded": state_sh is not None,
        "executables": 1 if n_exec is None else int(n_exec),
        "steps": int(len(m["loss"])),
        "first_loss": float(m["loss"][0]),
        "final_loss": float(np.mean(m["loss"][-q:])),
        "final_consensus_x": float(np.mean(m["consensus_x"][-q:])),
        "wire_mb_per_round": float(m["wire_bytes"][-1] / 1e6),
        "loss_curve": m["loss"][:: max(len(m["loss"]) // 40, 1)].tolist(),
        "agent_rounds_per_s": (float(n * timed_rounds / elapsed)
                               if elapsed > 0 else None),
        "s_per_round": (float(elapsed / timed_rounds)
                        if timed_rounds else None),
    }
    assert np.isfinite(m["loss"]).all(), f"n={n}: non-finite loss"
    assert rec["final_loss"] < rec["first_loss"], (
        f"n={n}: no convergence ({rec['first_loss']:.4f} -> "
        f"{rec['final_loss']:.4f})")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="rounds per rung (default 200, or 24 with --smoke)")
    ap.add_argument("--algo", default="clip21",
                    help="registered fleet-compatible algorithm")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: n=256 only")
    args = ap.parse_args()
    steps = args.steps or (24 if args.smoke else 200)
    rungs = RUNGS[:1] if args.smoke else RUNGS

    rows = []
    for n in rungs:
        rec = run_rung(n, steps, args.algo)
        rows.append(rec)
        aps = rec["agent_rounds_per_s"]
        print(f"fleet/{n},final_loss={rec['final_loss']:.4f},"
              f"consensus={rec['final_consensus_x']:.3e},"
              f"gap={rec['spectral_gap']:.4f},"
              f"sparse={int(rec['sparse_path'])},"
              f"agent_rounds_per_s={0.0 if aps is None else aps:.0f},"
              f"executables={rec['executables']}")

    # one executable per rung, across the whole n sweep
    assert all(r["executables"] == 1 for r in rows), rows

    art = Path("artifacts/bench")
    art.mkdir(parents=True, exist_ok=True)
    (art / "fleet_ablation.json").write_text(json.dumps(rows, indent=2))
    record = {"bench": "fleet_ablation", "algo": args.algo, "steps": steps,
              "smoke": bool(args.smoke), "protocol": {
                  "topology": "exponential/metropolis",
                  "compressor": "top_k", "frac": 0.05, "tau": 1.0,
                  "eta": 0.05, "dirichlet_alpha": ALPHA_DIR,
                  "shard": SHARD, "batch": BATCH},
              "rungs": rows}
    root = Path(__file__).resolve().parents[1]
    (root / "BENCH_fleet.json").write_text(
        json.dumps(record, indent=2) + "\n")
    print(f"# wrote {root / 'BENCH_fleet.json'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
