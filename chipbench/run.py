#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload minicpm3-gc --seed 7 --seconds 6 --trace 0

Steps, in order:

1. refuse a machine where JAX finds no TPU, or fewer chips than the cell
   asks for (exit non-zero, no result);
2. build the cell from its files (``cell.py``: ``configs/``, ``traffic/``,
   ``limits/``, named by BENCHMARK.json);
3. set up: weights and PORTER state on the device from ``--seed`` in one
   jitted call, the chunk program from the compile cache in the checkout,
   and the first chunk of rounds -- the warm-up, and the rounds that the
   reference follows;
4. measure: whole chunks through ``repro.launch.runtime.make_runner``'s
   runner, each ending on ``block_until_ready``, for ``--seconds``; with
   ``--trace 1`` under the profiler;
5. check: the plain reference follows the first chunk's rounds from the
   seed, and each compared number is held to its limit;
6. print the numbers compared (standard error, last lines) and the result
   line (standard output, last line).

``setup_s`` runs from the start of this process to the end of the warm-up
chunk.  The reference's time is not counted anywhere.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache(jax) -> None:
    """JAX's persistent cache at the checkout's fixed directory, every
    program kept."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def count_programs(jax):
    """Counters of compile-cache hits and misses and of the programs built
    (compiled or loaded from the cache), kept up to date until the returned
    ``stop`` is called."""
    counts = {"cache_hits": 0, "cache_misses": 0, "programs": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["cache_misses"] += 1

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            counts["programs"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def stop():
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)

    return counts, stop


def check_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found platform {devs[0].platform!r}"
                         ", not a TPU; nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs


def load_peaks(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         "peaks.json")
    return peaks[kind]


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, kind: str, workload: str) -> list:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def state_norms(cell, state, kw):
    """Per-(agent, leaf) norms of G_prev, v, m_v and x - x0 after the first
    chunk, with x0 made again from the weights key."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.cell import make_params

    def norms(leaves):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            l.astype(jnp.float32).reshape(l.shape[0], -1)), axis=1))
            for l in leaves], axis=1)

    @jax.jit
    def read(state, key):
        x0 = jax.tree_util.tree_leaves(make_params(cell.param_shapes, key))
        lv = jax.tree_util.tree_leaves
        return {"grad": norms(lv(state.g_prev)), "v": norms(lv(state.v)),
                "m_v": norms(lv(state.m_v)),
                "dx": norms([x - a[None] for x, a in zip(lv(state.x), x0)])}

    return {k: np.asarray(v) for k, v in read(state, kw).items()}


def device_peak(devs) -> int:
    """Peak device memory on the fullest chip: the runtime's peak of live
    buffers (the state, the batch, the outputs) plus its peak of reserved
    bytes, where the TPU runtime keeps the programs' scratch while they
    run (in use + reserved + free is the chip's limit)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"[memory] {d}: {stats}")
        peak = max(peak, stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return peak


def run(argv=None, *, require_tpu: bool = True, build_kw=None,
        bench_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """One run of a cell; returns the result line's object.

    ``require_tpu``/``build_kw``/``bench_path`` are for the CPU tests, which
    drive everything after the platform check at a small size.
    """
    args = parse(argv)
    if require_tpu:
        # the program, should it set a cache itself, takes the benchmark's;
        # the TPU runtime writes no log files outside the checkout
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np

    from chipbench import cell as C
    from chipbench import counts as K
    from chipbench.reference import Reference, compare

    if require_tpu:
        use_cache(jax)
    counters, stop_counting = count_programs(jax)
    entry, bench, config, traffic, limits = C.load_cell(args.workload,
                                                        bench_path)
    chips = entry["chips"]
    devs = check_devices(jax, chips) if require_tpu else jax.devices()
    dev = devs[0]
    peaks = load_peaks(dev.device_kind) if require_tpu else None
    log(f"[cell] {args.workload}: {entry['config']} x {entry['traffic']} on "
        f"{dev.platform} {dev.device_kind} x{len(devs)}, seed {args.seed}")

    # ---- set-up ---------------------------------------------------------
    cell = C.build(config, traffic, **(build_kw or {}))
    kw, kr = C.stream_keys(args.seed)
    if cell.key_sharding is not None:
        kr = jax.device_put(kr, cell.key_sharding)
    state = cell.init(kw)
    chunk = traffic["chunk"]
    state, key, m0 = cell.runner(state, kr, 0)
    jax.block_until_ready((state, m0))
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f}s; {counters['programs']} programs built, "
        f"compile cache hits {counters['cache_hits']}, misses "
        f"{counters['cache_misses']}")
    prog_losses = np.asarray(m0["loss"], np.float64)
    prog_norms = state_norms(cell, state, kw)
    wire = float(np.asarray(m0["wire_bytes"])[-1])
    log(f"[wire] {wire:.0f} bytes per round ({traffic['compressor']} at rho "
        f"{traffic['frac']}, {traffic['wire']} wire)")

    # ---- the measured window --------------------------------------------
    built = counters["programs"]
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t, rounds, last, losses = chunk, 0, None, []
    tw0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if last is not None and (now - tw0) + last > args.seconds:
            break
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            state, key, m = cell.runner(state, key, t)
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready((state, m))
        last = time.perf_counter() - now
        losses.extend(np.asarray(m["loss"]).tolist())
        t += chunk
        rounds += chunk
    tw1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    window_s = tw1 - tw0
    stop_counting()
    if counters["programs"] != built:
        raise RuntimeError(f"{counters['programs'] - built} programs built "
                           "inside the window")
    tokens_per_s = cell.tokens_per_round * rounds / window_s
    log(f"[window] {rounds} rounds in {window_s:.3f}s: {tokens_per_s:.3f} "
        "tokens/s")

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
    state_shapes = jax.tree_util.tree_map(like, state)
    key_shape = like(key)
    peak = device_peak(devs[:chips])
    del state, m, m0, key

    # ---- per-layer readings from the trace ------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {}
    if args.trace:
        import jax.numpy as jnp
        from chipbench import trace as T
        # the device events name HLO instructions; their op_names (with the
        # benchmark's named scopes) come from the compiled chunk program
        compiled = cell.runner.jitted.lower(
            state_shapes, key_shape,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        tr = T.reduce(T.trace_file(trace_dir), T.op_names(compiled.as_text()))
        del compiled
        evs = [es for _, es in sorted(tr.devices.items())][:chips]
        lo, hi = tr.window()
        ctx = dict(trace=tr, devices=evs, lo=lo, hi=hi, window_s=window_s,
                   rounds=rounds, tokens_per_s=tokens_per_s, chips=chips,
                   peaks=peaks, traffic=traffic, config=config,
                   flops_per_token=K.flops_per_token(config, traffic["seq"]),
                   round_bytes=K.engine_round_bytes(
                       K.state_leaf_bytes(state_shapes)))
        per_layer = {}
        for mdef in cell_metrics(bench, "per_layer", args.workload):
            val = load_reader(mdef["name"])(ctx)
            if val is not None:
                per_layer[mdef["name"]] = {"value": val, "unit": mdef["unit"]}
        busy = sum(T.busy_ns(es, lo, hi) for es in evs) / len(evs) / 1e9
        device.update(busy_s=busy, window_s=(hi - lo) / 1e9)
        result["metrics"] = per_layer
        result["breakdown"] = {
            "device_ops": T.top_ops(evs[0], lo, hi),
            "idle_gaps": T.idle_gaps(evs[0], tr.host, lo, hi)}
    else:
        e2e = {"tokens_per_s": tokens_per_s, "peak_hbm_gib": peak / 2**30,
               "setup_s": setup_s}
        result["metrics"] = {
            mdef["name"]: {"value": e2e[mdef["name"]], "unit": mdef["unit"]}
            for mdef in cell_metrics(bench, "end_to_end", args.workload)}

    # ---- check against the plain reference ------------------------------
    t0 = time.perf_counter()
    ref = Reference(config, traffic, cell.param_shapes)
    with jax.default_matmul_precision("highest"):
        ref_losses, ref_norms, _ = ref.run(args.seed, chunk)
    nums = compare(prog_losses, prog_norms, ref_losses, ref_norms)
    finite = bool(np.all(np.isfinite(losses)))
    failed = int(np.sum(~np.isfinite(np.asarray(losses))))
    correct = finite and all(
        math.isfinite(nums[k]) and nums[k] <= limits[k] for k in limits)
    log(f"[check] reference {time.perf_counter() - t0:.3f}s; program losses "
        f"{prog_losses.tolist()} reference {ref_losses.tolist()}")
    check = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    for k, v in check.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": bool(correct), "attempted": rounds, "failed": failed,
           **result, "device": device, "check": check}
    return out


def main(argv=None) -> int:
    out = run(argv)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
