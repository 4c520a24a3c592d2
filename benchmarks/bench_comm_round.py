"""Comm-round engine microbenchmark: fused (Pallas) vs reference (jnp)
round time and wire bytes/round across compressors.

One PORTER iteration outside the model is two comm rounds (track + step)
over every parameter: ~13 HBM-bound passes unfused, 7 reads + 4 writes per
round fused (see EXPERIMENTS.md #Perf).  This harness times exactly that
slice -- gradients excluded -- for the engine's two backends:

    ref     pure-jnp tree_map chain (XLA-fused on CPU; the oracle)
    pallas  flat tile planes + ef_track/ef_step kernels
            (Mosaic on TPU; interpret mode on CPU, where it is *slower* --
            interpret exists for correctness CI, the speedup is a TPU
            number)

``--sharded`` adds the model-sharded case: a (data x model) mesh whose
buffers carry model-parallel PartitionSpecs, ref vs the pallas per-shard
planes path (pack/unpack inside shard_map; the layout the launch layer
uses for tensor-parallel training).  Off-TPU this forces
--xla_force_host_platform_device_count=8 host devices.

``--achieved-bytes`` adds the bit-packed wire-format audit: engines built
with ``wire='packed_bits'`` on a 4-agent mesh, asserting the *measured*
shipped-buffer nbytes (``CommRound.wire_bytes``, via jax.eval_shape over
the codec) equals the analytic layout model (``wire_bytes_model``) for the
ring and packed collectives with both registered formats (``topk_bits``,
``qsgd_bits``), and reporting the dense-f32-vs-packed bandwidth ratio plus
the overlap-vs-sequential round time.  Every invocation also writes the
perf-trajectory baseline ``BENCH_comm.json`` at the repo root.

Usage:
    PYTHONPATH=src python benchmarks/bench_comm_round.py            # full
    PYTHONPATH=src python benchmarks/bench_comm_round.py --smoke    # CI
    PYTHONPATH=src python benchmarks/bench_comm_round.py --smoke --sharded
    PYTHONPATH=src python benchmarks/bench_comm_round.py --smoke --achieved-bytes

Rows: compressor,backend,us_per_round,bytes_per_round
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):  # allow `python benchmarks/bench_comm_round.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# must precede the jax import: device count locks at first backend init
if "--sharded" in sys.argv or "--achieved-bytes" in sys.argv:
    from repro._env import ensure_host_device_count
    ensure_host_device_count(8)

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ExperimentSpec, build_engine, resolve_compressor
from repro.launch.mesh import make_mesh

# the paper's sparse family; 'rand_k' is the registry's random_k
COMPRESSORS = (("top_k", "top_k"), ("block_top_k", "block_top_k"),
               ("rand_k", "random_k"))


def make_buffers(key, n_agents: int, d: int):
    """Agent-stacked PORTER-shaped buffers with odd, non-tile-aligned leaves."""
    d1 = max(d - d // 3 - 1, 1)
    d2 = d - d1
    shapes = {"w": (d1,), "b": (d2,)} if d2 else {"w": (d1,)}
    ks = jax.random.split(key, 7)

    def tree(k):
        sub = jax.random.split(k, len(shapes))
        return {name: jax.random.normal(kk, (n_agents,) + s)
                for kk, (name, s) in zip(sub, shapes.items())}

    # (y, q, m) for the buffer plus (g, g_prev) for the track side
    return tuple(tree(k) for k in ks[:5])


def timed_us(fn, *args, reps: int):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def bench(n_agents: int, d: int, frac: float, reps: int):
    base = ExperimentSpec(n_agents=n_agents, topology="ring",
                          topology_weights="metropolis", frac=frac,
                          interpret=None if jax.default_backend() == "tpu"
                          else True)
    key = jax.random.PRNGKey(0)
    y, q, m, g, gp = make_buffers(key, n_agents, d)
    gamma, eta = 0.1, 0.05

    print(f"# comm-round bench: n_agents={n_agents} d={d} frac={frac} "
          f"reps={reps} backend_device={jax.default_backend()}")
    print("compressor,backend,us_per_round,bytes_per_round")
    rows = []
    for label, reg_name in COMPRESSORS:
        for backend in ("ref", "pallas"):
            eng = build_engine(base.replace(compressor=reg_name,
                                            comm_backend=backend))

            @jax.jit
            def one_round(key, y, q, m, g, gp, eng=eng):
                k1, k2 = jax.random.split(key)
                v, q2, m2 = eng.track(k1, y, q, m, g, gp, gamma)
                x, q3, m3 = eng.step(k2, y, q2, m2, v, gamma, eta)
                return x, v, q3, m3

            us = timed_us(one_round, key, y, q, m, g, gp, reps=reps)
            wire = 2.0 * eng.wire_bytes(y)  # track + step streams
            rows.append((label, backend, us, wire))
            print(f"{label},{backend},{us:.1f},{wire:.0f}", flush=True)
    # headline: fused-vs-reference ratio per compressor
    for label, _ in COMPRESSORS:
        r = {b: us for (l, b, us, _) in rows if l == label}
        print(f"# {label}: pallas/ref time ratio = "
              f"{r['pallas'] / r['ref']:.2f} "
              f"(interpret mode is correctness-only off-TPU)")
    return rows


def bench_sharded(d: int, frac: float, reps: int):
    """Model-sharded case: (data=4, model=2) mesh, per-shard pallas planes
    vs the jnp reference, ring wire format, shard-local compression --
    the engine exactly as the tensor-parallel launch path builds it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.steps import make_shard_local_compress

    n_data, n_model = 4, 2
    if len(jax.devices()) < n_data * n_model:
        print(f"# sharded bench skipped: needs {n_data * n_model} devices, "
              f"have {len(jax.devices())} (run with --sharded from the CLI "
              "so the host-device flag is set before jax init)")
        return []
    mesh = make_mesh((n_data, n_model), ("data", "model"))
    n = n_data
    d_sh = max(d - d // 3 - 1, 2) // (2 * n_model) * (2 * n_model)
    d_rep = max(d - d_sh, 1)
    shapes = {"w": (d_sh // (2 * n_model), 2 * n_model), "b": (d_rep,)}
    specs = {"w": P("data", None, "model"), "b": P("data", None)}
    sh = {k: NamedSharding(mesh, specs[k]) for k in specs}
    key = jax.random.PRNGKey(0)

    def tree(k):
        ks = jax.random.split(k, len(shapes))
        return {name: jax.device_put(
                    jax.random.normal(kk, (n,) + shapes[name]), sh[name])
                for kk, name in zip(ks, shapes)}

    y, q, m, g, gp = (tree(k) for k in jax.random.split(key, 5))
    gamma, eta = 0.1, 0.05
    base = ExperimentSpec(n_agents=n, topology="ring",
                          topology_weights="metropolis",
                          compressor="block_top_k", frac=frac,
                          gossip_mode="ring",
                          interpret=None if jax.default_backend() == "tpu"
                          else True)
    shard_local = make_shard_local_compress(resolve_compressor(base), mesh,
                                            specs)

    print(f"# sharded comm-round bench: mesh=(data={n_data},model={n_model}) "
          f"d={d} frac={frac} reps={reps}")
    print("compressor,backend,us_per_round,bytes_per_round")
    rows = []
    for backend in ("ref", "pallas"):
        eng = build_engine(base.replace(comm_backend=backend), mesh=mesh,
                           leaf_specs=specs, compress_fn=shard_local)

        @jax.jit
        def one_round(key, y, q, m, g, gp, eng=eng):
            k1, k2 = jax.random.split(key)
            v, q2, m2 = eng.track(k1, y, q, m, g, gp, gamma)
            x, q3, m3 = eng.step(k2, y, q2, m2, v, gamma, eta)
            return x, v, q3, m3

        us = timed_us(one_round, key, y, q, m, g, gp, reps=reps)
        wire = 2.0 * eng.wire_bytes(y)
        rows.append(("block_top_k/sharded", backend, us, wire))
        print(f"block_top_k/sharded,{backend},{us:.1f},{wire:.0f}",
              flush=True)
    return rows


def bench_achieved_bytes(reps: int):
    """Bit-packed wire-format audit on a 4-agent mesh.

    For every (format x collective) pair the engine is built exactly as the
    launch layer builds it (``wire='packed_bits'`` through the api facade)
    and three numbers are pinned:

      measured   CommRound.wire_bytes      -- nbytes of the shipped buffers
                                              (traced shapes of codec.pack)
      model      CommRound.wire_bytes_model -- windows x layout constants
      dense      the same collective shipping dense f32 planes

    measured == model is asserted exactly (the PR-3 drift-bug class);
    the acceptance ratios count *payload* bytes (per-window f32 scales are
    overhead, reported separately): >= 4x for top-k frac=0.25, >= 8x for
    qsgd with the 4-bit (s=16 signed alphabet) code words.  The buffer
    sizes use a window-aligned d -- padding is a property of the problem
    shape, not of the wire format, so the audit excludes it.

    Also times the ring/topk engine sequential vs overlapped (both
    exchanges issued before either fused update) and asserts the two
    orderings are bit-exact.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import wire_formats as WF

    n = 4
    if len(jax.devices()) < n:
        print(f"# achieved-bytes audit skipped: needs {n} devices, have "
              f"{len(jax.devices())} (run --achieved-bytes from the CLI so "
              "the host-device flag is set before jax init)")
        return None
    mesh = make_mesh((n,), ("data",))
    windows = 8
    d = windows * WF.PACK_BLOCK                     # window-aligned
    specs = {"w": P("data", None)}
    sh = NamedSharding(mesh, specs["w"])
    key = jax.random.PRNGKey(0)

    def tree(k):
        return {"w": jax.device_put(jax.random.normal(k, (n, d)), sh)}

    y, q, m, g, gp = (tree(k) for k in jax.random.split(key, 5))
    gamma, eta = 0.1, 0.05
    interpret = None if jax.default_backend() == "tpu" else True
    base = ExperimentSpec(n_agents=n, topology="ring",
                          topology_weights="metropolis", wire="packed_bits",
                          comm_backend="ref", interpret=interpret)
    cases = [
        ("topk_bits", "ring",
         dict(compressor="block_top_k", frac=0.25, gossip_mode="ring")),
        ("topk_bits", "packed",
         dict(compressor="block_top_k", frac=0.25, gossip_mode="packed")),
        ("qsgd_bits", "ring",
         dict(compressor="qsgd", compressor_kwargs={"levels": 7},
              gossip_mode="ring")),
        ("qsgd_bits", "packed",
         dict(compressor="qsgd", compressor_kwargs={"levels": 7},
              gossip_mode="packed")),
    ]
    print(f"# achieved-bytes audit: n_agents={n} d={d} "
          f"(window-aligned, {windows} windows)")
    print("format,mode,us_per_round,measured_bytes,model_bytes,"
          "dense_bytes,payload_ratio,total_ratio")
    out = {"n_agents": n, "d": d, "cases": []}
    engines = {}
    for fmt, mode, kw in cases:
        eng = build_engine(base.replace(**kw), mesh=mesh, leaf_specs=specs)
        engines[(fmt, mode)] = eng
        measured = eng.wire_bytes(y)
        model = eng.wire_bytes_model(y)
        assert measured == model, \
            f"{fmt}/{mode}: measured {measured} != model {model}"
        codec = eng.mixer.wire_codec
        mult = (1.0 if n == 2 else 2.0) if mode == "ring" else float(n)
        dense = mult * d * 4.0                       # dense f32 planes
        overhead = mult * windows * codec.overhead_bytes_per_window
        payload_ratio = dense / (measured - overhead)
        total_ratio = dense / measured

        @jax.jit
        def one_round(key, y, q, m, g, gp, eng=eng):
            k1, k2 = jax.random.split(key)
            v, q2, m2 = eng.track(k1, y, q, m, g, gp, gamma)
            x, q3, m3 = eng.step(k2, y, q2, m2, v, gamma, eta)
            return x, v, q3, m3

        us = timed_us(one_round, key, y, q, m, g, gp, reps=reps)
        print(f"{fmt},{mode},{us:.1f},{measured:.0f},{model:.0f},"
              f"{dense:.0f},{payload_ratio:.3f},{total_ratio:.3f}",
              flush=True)
        out["cases"].append(dict(
            format=fmt, mode=mode, us_per_round=us,
            measured_bytes=measured, model_bytes=model, dense_bytes=dense,
            payload_ratio=payload_ratio, total_ratio=total_ratio))
        floor = 4.0 if fmt == "topk_bits" else 8.0
        assert payload_ratio >= floor, \
            f"{fmt}/{mode}: payload ratio {payload_ratio:.3f} < {floor}x"

    # ---- directed push-sum: the weight scalar rides the codec wire ----
    # dp-csgp ships one exact f32 push-sum weight per agent, bitcast into
    # words of the codec's last wire buffer (+4 bytes per shipped buffer
    # set).  The measured path derives those 4 bytes from the codec's pack
    # signature (wire_formats.measured_weight_nbytes), so measured == model
    # must hold with push_sum=True exactly as it does for the plain rounds,
    # and the delta over the plain round is exactly the collective's
    # shipped-copies multiplier x 4.
    # ring executor needs circulant +-1 bands -> the skip-0 directed ring;
    # packed ships whole tables, so it takes a genuinely asymmetric
    # (one-way link loss) column-stochastic schedule
    dscheds = {"ring": "directed:ring_skips",
               "packed": "directed:one_way,rate=0.3,period=4,skip=2"}
    dbase = base.replace(compressor="block_top_k", frac=0.25)
    ps_rows = []
    for mode in ("ring", "packed"):
        eng = build_engine(dbase.replace(gossip_mode=mode,
                                         topology_schedule=dscheds[mode]),
                           mesh=mesh, leaf_specs=specs)
        plain = eng.wire_bytes(y)
        ps_meas = eng.wire_bytes(y, push_sum=True)
        ps_model = eng.wire_bytes_model(y, push_sum=True)
        assert ps_meas == ps_model, \
            f"directed/{mode}: push-sum measured {ps_meas} != model {ps_model}"
        mult = (1.0 if n == 2 else 2.0) if mode == "ring" else float(n)
        assert ps_meas - plain == mult * 4.0, \
            f"directed/{mode}: weight bytes {ps_meas - plain} != {mult * 4.0}"

        xw = jnp.ones((n,), jnp.float32)
        qw = jnp.zeros((n,), jnp.float32)

        @jax.jit
        def ps_round(key, y, q, xw, qw, eng=eng):
            return eng.exchange_ps(key, y, q, xw, qw,
                                   t=jnp.zeros((), jnp.int32))

        c, wc, cw, wcw = ps_round(key, y, q, xw, qw)
        # column-stochastic W conserves weight mass: 1^T(W cw) == 1^T cw
        mass_in = float(np.asarray(jnp.sum(cw)))
        mass_out = float(np.asarray(jnp.sum(wcw)))
        assert abs(mass_in - mass_out) < 1e-4, (mode, mass_in, mass_out)
        print(f"# directed/{mode}: push_sum bytes {ps_meas:.0f} "
              f"(plain {plain:.0f} + weight {ps_meas - plain:.0f}), "
              f"weight mass {mass_in:.6f} -> {mass_out:.6f}", flush=True)
        ps_rows.append(dict(mode=mode, plain_bytes=plain,
                            push_sum_bytes=ps_meas,
                            weight_bytes=ps_meas - plain))
    out["directed_push_sum"] = ps_rows

    # ---- overlap: both exchanges in flight before either fused update ----
    # PORTER's two rounds run over *independent* buffer pairs -- (v, q_v)
    # and (x, q_x) -- which is exactly why the reorder is bit-exact: the
    # x-side exchange reads nothing the track update writes
    eng = engines[("topk_bits", "ring")]
    q_x, m_x = tree(jax.random.PRNGKey(7)), tree(jax.random.PRNGKey(8))

    @jax.jit
    def seq_round(key, y, q, m, g, gp, q_x, m_x):
        k1, k2 = jax.random.split(key)
        v, q2, m2 = eng.track(k1, y, q, m, g, gp, gamma)
        x, q3, m3 = eng.step(k2, y, q_x, m_x, v, gamma, eta)
        return x, v, q2, q3, m2, m3

    @jax.jit
    def ovl_round(key, y, q, m, g, gp, q_x, m_x):
        k1, k2 = jax.random.split(key)
        c_v, wc_v = eng.exchange(k1, y, q)
        c_x, wc_x = eng.exchange(k2, y, q_x)
        v, q2, m2 = eng.track_update(c_v, wc_v, y, q, m, g, gp, gamma)
        x, q3, m3 = eng.step_update(c_x, wc_x, y, q_x, m_x, v, gamma, eta)
        return x, v, q2, q3, m2, m3

    a = seq_round(key, y, q, m, g, gp, q_x, m_x)
    b = ovl_round(key, y, q, m, g, gp, q_x, m_x)
    bitexact = all(
        np.array_equal(np.asarray(la), np.asarray(lb))
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)))
    assert bitexact, "overlap ordering is not bit-exact to sequential"
    seq_us = timed_us(seq_round, key, y, q, m, g, gp, q_x, m_x, reps=reps)
    ovl_us = timed_us(ovl_round, key, y, q, m, g, gp, q_x, m_x, reps=reps)
    eff = seq_us / ovl_us
    print(f"# overlap(topk_bits/ring): seq={seq_us:.1f}us ovl={ovl_us:.1f}us "
          f"efficiency={eff:.2f}x bitexact={bitexact} "
          "(overlap is a latency-hiding number on TPU; CPU shows parity)")
    out["overlap"] = dict(format="topk_bits", mode="ring", seq_us=seq_us,
                          ovl_us=ovl_us, efficiency=eff, bitexact=bitexact)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CPU CI")
    ap.add_argument("--sharded", action="store_true",
                    help="add the model-sharded (per-shard planes) case")
    ap.add_argument("--achieved-bytes", action="store_true",
                    help="audit measured vs modeled bit-packed wire bytes "
                         "(ring/packed x topk_bits/qsgd_bits) + overlap")
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--d", type=int, default=None,
                    help="per-agent parameter count")
    ap.add_argument("--frac", type=float, default=0.05)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args(argv)

    if args.smoke:
        n, d, reps = 4, 20_001, 3
    else:
        n, d, reps = 8, 1_000_003, 10
    n = args.agents or n
    d = args.d or d
    reps = args.reps or reps
    rows = bench(n, d, args.frac, reps)
    record = {
        "bench": "comm_round", "device_backend": jax.default_backend(),
        "smoke": bool(args.smoke), "n_agents": n, "d": d,
        "frac": args.frac, "reps": reps,
        "rounds": [dict(compressor=l, backend=b, us_per_round=us,
                        steps_per_s=1e6 / us, bytes_per_round=w)
                   for (l, b, us, w) in rows],
    }
    if args.sharded:
        srows = bench_sharded(d, args.frac, reps)
        record["sharded"] = [
            dict(compressor=l, backend=b, us_per_round=us,
                 steps_per_s=1e6 / us, bytes_per_round=w)
            for (l, b, us, w) in srows]
    if args.achieved_bytes:
        record["achieved_bytes"] = bench_achieved_bytes(reps)
    # perf-trajectory baseline: future PRs diff against the checked-in copy
    out = Path(__file__).resolve().parents[1] / "BENCH_comm.json"
    out.write_text(json.dumps(record, indent=2) + "\n")
    print(f"# wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
