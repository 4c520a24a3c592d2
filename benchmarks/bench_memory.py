"""Mixed-precision memory benchmark: f32 vs bf16 state planes + remat.

Three measurements, each reported f32-vs-bf16 (``plane_dtype``):

* **resident plane bytes** -- the EF/gossip state buffers (q, m, v,
  g_prev; everything but the f32 master params and the step counter),
  summed from the initialized state.  The acceptance gate asserts the
  bf16 engine cuts these by >= 1.9x.
* **gossip wire bytes** -- measured two ways: the engine's per-round
  accounting (the ``wire_bytes`` metric out of the chunked runner) and
  the compiled program itself (collective result bytes attributed to the
  gossip executor in the optimized HLO, via repro.analysis.hlo).  The
  HLO measurement is the load-bearing one: bf16 planes must ship
  <= 2 B/elem (they cross as their u16 bit pattern, like the codec
  executors), and the gate asserts >= 1.9x there too.
* **steps/s + parity** -- the paper's Section-5.1 logreg protocol
  (10 agents, ER(0.8), random-5% compression) through the chunked
  runtime; the bf16 engine must land its final loss within tolerance of
  the f32 run (stochastic rounding keeps the EF recursion unbiased, so
  the curves track).

The ``--lm`` leg builds the tinyllama-1.1b smoke config with
``remat_policy='dots'`` + bf16 planes, compiles it, and runs one chunk --
``compiled.memory_analysis()`` live-bytes are recorded when the backend
reports them (TPU; CPU returns nothing and the field stays null).

Rows land in artifacts/bench/memory.json and the perf-trajectory copy
BENCH_memory.json (future PRs diff against the checked-in file).

    PYTHONPATH=src python benchmarks/bench_memory.py            # full
    PYTHONPATH=src python benchmarks/bench_memory.py --smoke    # CI
    PYTHONPATH=src python benchmarks/bench_memory.py --no-lm    # skip lm leg
"""

from __future__ import annotations

from repro._env import ensure_host_device_count

ensure_host_device_count(8)

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as H
from repro.api import ExperimentSpec, build
from repro.data import a9a_like, minibatch_source, shard_to_agents
from repro.launch.mesh import make_mesh
from repro.launch.runtime import make_runner

# the paper's Section-5.1 protocol (standalone, like bench_train_loop.py)
N_AGENTS = 10
PAPER_SPEC = ExperimentSpec(n_agents=N_AGENTS, topology="erdos_renyi",
                            topology_weights="best_constant", topology_p=0.8,
                            topology_seed=1)

PLANE_RATIO_FLOOR = 1.9
PARITY_TOL = 0.02      # |final_loss(f32) - final_loss(bf16)| on Section 5.1

# wire-measurement problem: 4 host agents on a ring, one flat leaf big
# enough that plane traffic dwarfs scalar riders
WIRE_N, WIRE_D = 4, 4096


def _logreg_loss(params, batch):
    f, l = batch
    f = jnp.atleast_2d(f)
    l = jnp.atleast_1d(l)
    logits = f @ params["w"] + params["b"]
    nll = jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))
    return nll + 0.2 * jnp.sum(params["w"] ** 2 / (1 + params["w"] ** 2))


def _spec(plane_dtype):
    return PAPER_SPEC.replace(algo="porter-gc", compressor="random_k",
                              frac=0.05, eta=0.05, tau=1.0,
                              plane_dtype=plane_dtype)


def _problem():
    x, y = a9a_like(12000, 123, seed=0)
    xs, ys = shard_to_agents(x, y, N_AGENTS)
    params0 = {"w": jnp.zeros(123), "b": jnp.zeros(())}
    return params0, minibatch_source(xs, ys, batch=4)


# ---------------------------------------------------------------------------
# Resident plane bytes.
# ---------------------------------------------------------------------------

def plane_bytes(state) -> dict:
    """Split the state's bytes into master params (x), EF/gossip planes
    (every other model-size buffer) and scalars (the step counter &c.)."""
    out = {"x": 0, "planes": 0, "other": 0}
    for name in state._fields:
        leaf_bytes = sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(getattr(state, name)))
        if name == "x":
            out["x"] += leaf_bytes
        elif leaf_bytes >= 4 * N_AGENTS:  # model-size agent-stacked buffer
            out["planes"] += leaf_bytes
        else:
            out["other"] += leaf_bytes
    return out


# ---------------------------------------------------------------------------
# Measured gossip wire bytes (optimized HLO, ring executor on a host mesh).
# ---------------------------------------------------------------------------

def _wire_loss(p, b):
    return jnp.mean((p["w"] - b) ** 2)


def hlo_gossip_bytes(plane_dtype) -> int:
    """Sum collective result bytes attributed to the gossip executor in the
    compiled porter-gc step (ring, 4 host agents)."""
    mesh = make_mesh((WIRE_N,), ("data",),
                     devices=jax.devices()[:WIRE_N])
    spec = ExperimentSpec(algo="porter-gc", n_agents=WIRE_N, topology="ring",
                          topology_weights="metropolis",
                          compressor="block_top_k", frac=0.25,
                          comm_backend="ref", interpret=True, eta=0.1,
                          gossip_mode="ring", plane_dtype=plane_dtype)
    algo = build(spec, _wire_loss, mesh=mesh)
    state = algo.init({"w": jnp.zeros(WIRE_D)})
    shard = lambda l: NamedSharding(
        mesh, P(*(("data",) + (None,) * (l.ndim - 1))
                if getattr(l, "ndim", 0) >= 1 and l.shape[0] == WIRE_N
                else ()))
    state = jax.device_put(state, jax.tree_util.tree_map(shard, state))
    batch = jax.device_put(jnp.zeros((WIRE_N, 1, WIRE_D)),
                           NamedSharding(mesh, P("data", None, None)))
    key = jax.device_put(jax.random.PRNGKey(0), NamedSharding(mesh, P()))
    hlo = jax.jit(algo.step).lower(state, batch, key).compile().as_text()
    return sum(op.result_bytes for op in H.collective_ops(hlo)
               if op.source in H.GOSSIP_SOURCES)


# ---------------------------------------------------------------------------
# Section-5.1 protocol: steps/s, engine wire accounting, parity.
# ---------------------------------------------------------------------------

def run_protocol(plane_dtype, steps: int, chunk: int) -> dict:
    params0, source = _problem()
    algo = build(_spec(plane_dtype), _logreg_loss)
    state = algo.init(params0)
    st = plane_bytes(state)

    runner = make_runner(algo, source, chunk)
    key = jax.random.PRNGKey(0)
    mem = compiled_memory(runner, state)
    state, key, metrics = runner(state, key, 0)  # warmup (compile)
    t0 = time.perf_counter()  # analysis: ok -- host wall-clock IS the measurement
    for t in range(chunk, steps, chunk):
        state, key, metrics = runner(state, key, t)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0  # analysis: ok -- host wall-clock
    return {
        "plane_dtype": plane_dtype or "f32",
        "state_bytes": st,
        "final_loss": float(metrics["loss"][-1]),
        "wire_bytes_per_round": float(metrics["wire_bytes"][-1]),
        "steps_per_s": (steps - chunk) / dt if steps > chunk else None,
        "memory_analysis": mem,
    }


def compiled_memory(runner, state) -> dict | None:
    """``compiled.memory_analysis()`` of the chunk executable, lowered
    abstractly from the state's shapes.  TPU reports full live-buffer
    accounting; the CPU backend exposes the same interface with partial
    fields, and anything missing stays out of the record."""
    shapes = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
    try:
        ma = runner.lower(shapes).compile().memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
    out = {}
    for f in fields:
        try:
            out[f] = int(getattr(ma, f))
        except Exception:
            continue
    return out or None


# ---------------------------------------------------------------------------
# LM leg: tinyllama-1.1b + loss-level remat + bf16 planes, one real chunk.
# ---------------------------------------------------------------------------

def run_lm(steps: int, chunk: int) -> dict:
    from repro.configs import get_smoke
    from repro.data import batch_source
    from repro.models import build_model
    cfg = get_smoke("tinyllama-1.1b")
    bundle = build_model(cfg)
    spec = ExperimentSpec(algo="porter-gc", n_agents=4, topology="ring",
                          compressor="top_k", frac=0.05, eta=3e-2, tau=1.0,
                          plane_dtype="bf16", remat_policy="dots")
    algo = build(spec, bundle.loss)
    params0, _ = bundle.init(jax.random.PRNGKey(0))
    state = algo.init(params0)
    st = plane_bytes(state)
    runner = make_runner(algo, batch_source(cfg, 4, 2, 64), chunk)
    key = jax.random.PRNGKey(0)
    mem = compiled_memory(runner, state)
    t0 = time.perf_counter()  # analysis: ok -- host wall-clock (compile+run)
    state, key, metrics = runner(state, key, 0)
    jax.block_until_ready(state)
    compile_s = time.perf_counter() - t0  # analysis: ok -- host wall-clock
    t0 = time.perf_counter()  # analysis: ok -- host wall-clock
    for t in range(chunk, steps, chunk):
        state, key, metrics = runner(state, key, t)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0  # analysis: ok -- host wall-clock
    return {
        "arch": "tinyllama-1.1b (smoke)", "remat_policy": "dots",
        "plane_dtype": "bf16", "state_bytes": st,
        "final_loss": float(metrics["loss"][-1]),
        "compile_plus_first_chunk_s": compile_s,
        "steps_per_s": (steps - chunk) / dt if steps > chunk else None,
        "memory_analysis": mem,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="protocol rounds (default 256, or 32 with --smoke)")
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--no-lm", action="store_true",
                    help="skip the tinyllama remat leg")
    args = ap.parse_args()
    steps = args.steps or (32 if args.smoke else 256)
    chunk = 8

    rows = [run_protocol(pd, steps, chunk) for pd in (None, "bf16")]
    f32, bf16 = rows
    plane_ratio = (f32["state_bytes"]["planes"]
                   / bf16["state_bytes"]["planes"])
    wire_model_ratio = (f32["wire_bytes_per_round"]
                        / bf16["wire_bytes_per_round"])

    hlo_bytes = {pd or "f32": hlo_gossip_bytes(pd) for pd in (None, "bf16")}
    hlo_ratio = hlo_bytes["f32"] / hlo_bytes["bf16"]
    loss_gap = abs(f32["final_loss"] - bf16["final_loss"])

    print("name,value,derived")
    print(f"memory/planes_f32,{f32['state_bytes']['planes']},"
          f"x_bytes={f32['state_bytes']['x']}")
    print(f"memory/planes_bf16,{bf16['state_bytes']['planes']},"
          f"ratio={plane_ratio:.2f}x")
    print(f"memory/wire_model,{bf16['wire_bytes_per_round']:.0f},"
          f"ratio={wire_model_ratio:.2f}x")
    print(f"memory/wire_hlo,{hlo_bytes['bf16']},"
          f"ratio={hlo_ratio:.2f}x;f32_bytes={hlo_bytes['f32']}")
    print(f"memory/parity,{bf16['final_loss']:.4f},"
          f"f32={f32['final_loss']:.4f};gap={loss_gap:.4f}")
    for r in rows:
        if r["steps_per_s"]:
            print(f"memory/steps_per_s/{r['plane_dtype']},"
                  f"{r['steps_per_s']:.1f},")

    record = {"bench": "memory", "steps": steps, "smoke": bool(args.smoke),
              "rows": rows, "plane_ratio": plane_ratio,
              "wire_model_ratio": wire_model_ratio,
              "wire_hlo_bytes": hlo_bytes, "wire_hlo_ratio": hlo_ratio,
              "parity_gap": loss_gap}
    if not args.no_lm:
        lm = run_lm(steps=max(2 * chunk, 2), chunk=chunk)
        record["lm"] = lm
        print(f"memory/lm_remat,{lm['final_loss']:.4f},"
              f"compile_s={lm['compile_plus_first_chunk_s']:.1f}")

    art = Path("artifacts/bench")
    art.mkdir(parents=True, exist_ok=True)
    (art / "memory.json").write_text(json.dumps(record, indent=2))
    root = Path(__file__).resolve().parents[1]
    (root / "BENCH_memory.json").write_text(
        json.dumps(record, indent=2) + "\n")
    print(f"# wrote {root / 'BENCH_memory.json'}")

    # acceptance gates
    assert plane_ratio >= PLANE_RATIO_FLOOR, \
        f"bf16 planes cut resident bytes {plane_ratio:.2f}x < " \
        f"{PLANE_RATIO_FLOOR}x"
    assert hlo_ratio >= PLANE_RATIO_FLOOR, \
        f"measured gossip wire reduction {hlo_ratio:.2f}x < " \
        f"{PLANE_RATIO_FLOOR}x -- a dense f32 plane is crossing the wire"
    assert wire_model_ratio >= 1.0, \
        f"wire accounting regressed under bf16 ({wire_model_ratio:.2f}x)"
    assert loss_gap <= PARITY_TOL, \
        f"bf16 final loss diverged from f32 by {loss_gap:.4f} > {PARITY_TOL}"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
