#!/usr/bin/env python3
"""Bring-up check: the decentralized trainer on a TPU chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 4-agent ring over four chips

Everything runs in this one process, which holds the chip.  One chip:

  (a) name the device, and stop unless JAX found a TPU;
  (b) one comm round at the configuration's full state size with the
      compiled Pallas kernels, then with the jnp reference, from the same
      state and keys: the compiled step must hold ``tpu_custom_call``; the
      EF updates of both backends, fed the same exchange output, must agree
      within ``EF_TOL``; and ``sr_cast`` must match ``sr_cast_ref`` bit for
      bit;
  (c) ``repro.launch.train.main`` for porter-gc, then porter-dp, a few
      scanned chunks each: exit code 0, finite losses, wire MB/round;
  (d) host wall times of compilation and of steady steps, printed as
      information -- this is a bring-up check, not a benchmark.

``--chips 4`` runs only the agent ring: one agent per chip, porter-gc under
the ring executor with the dense wire, the ring with the bit-packed wire
and dense gossip, from the same state, batches and keys.

The configuration is tinyllama-1.1b at its published widths with the depth
cut to one layer, 2 agents (4 with ``--chips 4``), bf16 EF planes, top_k at
rho = 0.05, per-agent batch 2 x 512 tokens.  minicpm3-4b at one layer was
the first choice; its Pallas step needs 17.3 GiB of the chip's 15.75 GiB.

The last line of standard output is one JSON object, printed only when
every phase passed; any failed phase exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
N_LAYERS = 1
AGENTS = 2
BATCH = 2
SEQ = 512
FRAC = 0.05
PLANES = "bf16"
STEPS = 4
CHUNK = 2
# the CPU parity tests' tolerance for the same f32 elementwise arithmetic
EF_TOL = dict(atol=1e-5, rtol=1e-5)
PARITY_CHUNKS = 8
# ring vs dense gossip: the same W @ c, summed in another order.  With bf16
# planes the mixed increment can differ by a bf16 ulp (2^-8 relative) per
# element and round, scaled by gamma into x; on 4 host devices the gaps
# after 3 steps were 1.9e-5 (loss) and 2.6e-6 (x), 50x and 400x inside
RING_LOSS_RTOL = 1e-3
RING_X_RTOL = 1e-3
# packed_bits vs dense-wire ring permute bytes: top_k at FRAC ships about
# FRAC x (value + index bits) per element, far under a quarter of
# the dense wire's bf16 element
PACKED_BYTES_MAX = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what: str) -> None:
    """Fail the phase (an exception, so it also holds under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def phase_device():
    """(a): the device JAX found; exits non-zero unless it is a TPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"[a] device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform {d.platform!r}, "
                         "not a TPU; nothing was run")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _config(n_layers: int = N_LAYERS):
    from repro.launch.train import model_config
    return model_config(ARCH, n_layers=n_layers)


def _spec(n_agents: int, backend: str, interpret):
    from repro.api import ExperimentSpec
    return ExperimentSpec(algo="porter-gc", n_agents=n_agents,
                          topology="ring", compressor="top_k", frac=FRAC,
                          plane_dtype=PLANES, comm_backend=backend,
                          interpret=interpret)


def _violation(a, b, atol, rtol):
    """max(|a - b| - (atol + rtol |b|)) over all elements (<= 0 passes)."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b) - (atol + rtol * jnp.abs(b)))


def phase_comm_round(cfg, n_agents: int = AGENTS, interpret: bool = False,
                     seed: int = 0):
    """(b): Pallas vs reference comm round at the full state size.

    ``interpret=True`` runs the kernels in the Pallas interpreter (a CPU
    rehearsal); then no ``tpu_custom_call`` is expected.
    """
    import jax
    import jax.numpy as jnp
    from repro.api import build_engine
    from repro.kernels import ops, ref
    from repro.models import build_model

    bundle = build_model(cfg)
    shapes = jax.eval_shape(lambda k: bundle.init(k)[0],
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    d = sum(l.size for l in leaves)
    log(f"[b] state: {n_agents} agents x {d} params ({cfg.name}, "
        f"{cfg.n_layers} layer), {PLANES} EF planes")
    bf16 = jnp.bfloat16

    def rand_tree(key, dtype, scale):
        ks = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            (scale * jax.random.normal(k, (n_agents,) + l.shape)
             ).astype(dtype) for k, l in zip(ks, leaves)])

    @jax.jit
    def make_state(key):
        kx, kq, km, kv = jax.random.split(key, 4)
        return (rand_tree(kx, jnp.float32, 0.02), rand_tree(kq, bf16, 0.02),
                rand_tree(km, bf16, 0.02), rand_tree(kv, bf16, 0.01))

    key = jax.random.PRNGKey(seed)
    x, q, m, v = make_state(key)
    gamma, eta = 0.3, 0.03
    engines = {b: build_engine(_spec(n_agents, b,
                                     interpret if b == "pallas" else None))
               for b in ("pallas", "ref")}

    # The exchange (c = C(x - q) narrowed to bf16, wc = W c) is the same
    # code on both backends; only the EF update after it differs.  Inside
    # one fused program XLA may keep such a bf16 value at f32 precision
    # (excess precision), so two whole rounds can differ by a bf16 ulp of c
    # or wc.  The updates are therefore compared from one materialized
    # exchange, and the whole rounds beside them as information.
    @jax.jit
    def exchange(key, x, q, m):
        eng = engines["ref"]
        k_c, sr_key = eng.sr_split(key, (q, m, x))
        c, wc = eng.exchange(k_c, x, q)
        return c, wc, sr_key

    c, wc, sr_key = exchange(key, x, q, m)
    updates = {}
    for backend, eng in engines.items():
        update = jax.jit(lambda c, wc, x, q, m, v, k, eng=eng: eng.step_update(
            c, wc, x, q, m, v, gamma, eta, sr_key=k))
        updates[backend] = update(c, wc, x, q, m, v, sr_key)
    del c, wc

    @jax.jit
    def x_excess(a, b):
        return jnp.max(jnp.stack([_violation(p, r, **EF_TOL) for p, r in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]))

    @jax.jit
    def ulp_gap(a, b):   # the two SR draws round one f32 value up or down
        def ulps(p, r):
            ip = jax.lax.bitcast_convert_type(p, jnp.int16).astype(jnp.int32)
            ir = jax.lax.bitcast_convert_type(r, jnp.int16).astype(jnp.int32)
            return jnp.max(jnp.abs(ip - ir))
        return jnp.max(jnp.stack([ulps(p, r) for p, r in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]))

    (xp, qp, mp), (xr, qr, mr) = updates["pallas"], updates["ref"]
    x_gap = float(x_excess(xp, xr))
    bf_gap = float(ulp_gap((qp, mp), (qr, mr)))
    log(f"[b] EF update pallas vs ref, same exchange output: x' (f32) max "
        f"excess over atol+rtol*|ref| = {x_gap:.3e}; q', m' (bf16, "
        f"stochastic rounding) max bit-pattern gap = {bf_gap:.0f} ulp")
    require(x_gap <= 0.0, f"x' outside {EF_TOL}: {x_gap}")
    require(bf_gap <= 1, f"q'/m' more than one bf16 ulp apart: {bf_gap}")
    x_update = {"pallas": xp, "ref": xr}
    del updates, xp, qp, mp, xr, qr, mr

    x_round = {}
    for backend, eng in engines.items():
        step = jax.jit(lambda k, x, q, m, v, eng=eng:
                       eng.step(k, x, q, m, v, gamma, eta))
        t0 = time.perf_counter()
        compiled = step.lower(key, x, q, m, v).compile()
        t_compile = time.perf_counter() - t0
        if backend == "pallas" and not interpret:
            n_calls = compiled.as_text().count("tpu_custom_call")
            log(f"[b] pallas round: {n_calls} tpu_custom_call ops in the "
                "compiled step")
            require(n_calls > 0, "no compiled Pallas kernel in the step")
        t0 = time.perf_counter()
        x_round[backend] = jax.block_until_ready(
            compiled(key, x, q, m, v))[0]
        log(f"[b] {backend} round: compile {t_compile:.3f}s, run "
            f"{time.perf_counter() - t0:.3f}s (host clock); x' max excess "
            f"over the update from the materialized exchange = "
            f"{float(x_excess(x_round[backend], x_update[backend])):.3e}")
    log(f"[b] whole rounds pallas vs ref (information): x' max excess = "
        f"{float(x_excess(x_round['pallas'], x_round['ref'])):.3e}")
    del x, q, m, v, x_round, x_update

    # the fused kernels alone, f32 outputs, over as many elements as the
    # state holds (every parameter of every agent), in PARITY_CHUNKS pieces
    # so that operands, outputs and reference fit beside each other
    rows = -(-n_agents * d // (PARITY_CHUNKS * 2048))
    shape = (rows, 2048)
    kw = {"interpret": interpret}
    kernels = {
        "ef_track": (7, lambda a: ops.ef_track(*a, gamma,
                                               out_dtype=jnp.float32, **kw),
                     lambda a: ref.ef_track_ref(*a, gamma)),
        "ef_step": (6, lambda a: ops.ef_step(*a, gamma, eta,
                                             out_dtype=jnp.float32, **kw),
                    lambda a: ref.ef_step_ref(*a, gamma, eta)),
        "ef_gossip": (5, lambda a: ops.ef_gossip(*a, gamma, 0.5,
                                                 out_dtype=jnp.float32, **kw),
                      lambda a: ref.ef_gossip_ref(*a, gamma, 0.5)),
    }
    # inputs are made by their own program: inside the compared one, XLA
    # may skip the bf16 rounding of a generated value (excess precision)
    make_inputs = jax.jit(lambda key, n, dtype: [
        jax.random.normal(k, shape, dtype) for k in jax.random.split(key, n)
    ], static_argnums=(1, 2))
    for name, (n_in, kern, oracle) in kernels.items():
        @jax.jit
        def check(a, kern=kern, oracle=oracle):
            got = kern(a)
            want = oracle([t.astype(jnp.float32) for t in a])
            return jnp.max(jnp.stack([_violation(g, w, **EF_TOL)
                                      for g, w in zip(got, want)]))
        gap = max(float(check(make_inputs(
            jax.random.fold_in(key, 100 * n_in + i), n_in, bf16)))
            for i in range(PARITY_CHUNKS))
        log(f"[b] {name} pallas vs ref over {PARITY_CHUNKS} x {shape} "
            f"elements: max excess over atol+rtol*|ref| = {gap:.3e}")
        require(gap <= 0.0, f"{name} outside {EF_TOL}: {gap}")

    @jax.jit
    def sr_mismatches(vals, key):
        got = ops.sr_cast(vals, key, interpret=interpret)
        want = ops.sr_cast_ref(vals, key)
        return jnp.sum(jax.lax.bitcast_convert_type(got, jnp.uint16)
                       != jax.lax.bitcast_convert_type(want, jnp.uint16))
    bad = sum(int(sr_mismatches(
        make_inputs(jax.random.fold_in(key, 999 + i), 1, jnp.float32)[0],
        jax.random.fold_in(key, i))) for i in range(PARITY_CHUNKS))
    log(f"[b] sr_cast vs sr_cast_ref over {PARITY_CHUNKS} x {shape} "
        f"elements, same bits: {bad} differing bf16 values")
    require(bad == 0, "sr_cast is not bit-identical to sr_cast_ref")


def phase_train(n_layers: int = N_LAYERS, batch: int = BATCH,
                seq: int = SEQ, steps: int = STEPS, chunk: int = CHUNK,
                extra=()):
    """(c) and (d): porter-gc then porter-dp through the trainer's own
    entry point, with host wall times of the first and later chunks."""
    import numpy as np
    from repro.launch.train import main as train_main

    for algo in ("porter-gc", "porter-dp"):
        with tempfile.TemporaryDirectory() as tmp:
            out = Path(tmp) / "history.json"
            argv = ["--arch", ARCH, "--n-layers", str(n_layers),
                    "--algo", algo, "--agents", str(AGENTS),
                    "--batch", str(batch), "--seq", str(seq),
                    "--plane-dtype", PLANES, "--compressor", "top_k",
                    "--frac", str(FRAC), "--steps", str(steps),
                    "--chunk", str(chunk), "--log-every", "1",
                    "--out", str(out), *extra]
            log(f"[c] train {' '.join(argv[:-2])}")
            rc = train_main(argv)
            hist = json.loads(out.read_text())
        losses = [h["loss"] for h in hist]
        wire = [h["wire_bytes"] / 1e6 for h in hist]
        log(f"[c] {algo}: exit {rc}, losses {losses}, wire "
            f"{wire[-1]:.3f} MB/round")
        require(rc == 0, f"{algo} exited {rc}")
        require(len(losses) == steps and np.all(np.isfinite(losses)),
                f"{algo} losses {losses}")
        require(all(w > 0 for w in wire), f"{algo} wire {wire}")
        walls = [h["wall_s"] for h in hist]
        first = walls[chunk - 1]
        steady = (walls[-1] - first) / (steps - chunk)
        log(f"[d] {algo}: first chunk (compile + {chunk} steps) "
            f"{first}s, then {steady}s per step (host clock, information "
            "only)")


def permute_bytes(hlo_text: str) -> int:
    """Result bytes of every collective-permute in a compiled program."""
    from repro.analysis.hlo import collective_ops
    return sum(op.result_bytes for op in collective_ops(hlo_text)
               if op.category == "collective-permute")


def phase_agent_ring(cfg, batch: int = BATCH, seq: int = SEQ,
                     steps: int = 3, n_chips: int = 4,
                     interpret: bool = False):
    """``--chips 4``: porter-gc with one agent per chip under the ring
    executor (dense and bit-packed wire) and dense gossip.

    ``interpret=True`` is a rehearsal on host devices, where the kernels
    run interpreted and no ``tpu_custom_call`` is expected.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import batch_source
    from repro.launch import shapes as SH
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_step

    mesh = make_host_mesh(n_chips, devices=jax.devices()[:n_chips])
    shape = SH.ShapeSpec("chip_smoke", seq, n_chips * batch, "train")
    source = batch_source(cfg, n_chips, batch, seq)
    key = jax.random.PRNGKey(1)
    runs = {}
    for name, kw in (("ring", dict(gossip_mode="ring")),
                     ("ring+packed_bits", dict(gossip_mode="ring",
                                               wire="packed_bits")),
                     ("dense", dict(gossip_mode="dense"))):
        setup = build_train_step(cfg, mesh, shape, variant="gc",
                                 compressor_name="top_k", frac=FRAC,
                                 plane_dtype=PLANES, remat=False, **kw)
        state = jax.jit(setup.init_state,
                        out_shardings=setup.state_shardings)(
                            jax.random.PRNGKey(0))
        make_batch = jax.jit(source, out_shardings=setup.batch_shardings)
        t0 = time.perf_counter()
        compiled = setup.jitted.lower(
            state, make_batch(key, 0), key).compile()
        t_compile = time.perf_counter() - t0
        text = compiled.as_text()
        permutes = [l for l in text.splitlines()
                    if "collective-permute" in l and "=" in l]
        wire_types = sorted({t for l in permutes
                             for t in ("u16", "u32", "bf16", "f32")
                             if f" {t}[" in l or f"({t}[" in l})
        log(f"[4] {name}: compile {t_compile:.3f}s, "
            f"{text.count('tpu_custom_call')} tpu_custom_call, "
            f"{len(permutes)} collective-permute lines, permute types "
            f"{wire_types}")
        losses = []
        t0 = time.perf_counter()
        for t in range(steps):
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            state, metrics = compiled(state, make_batch(kb, t), ks)
            losses.append(float(metrics["loss"]))
        log(f"[4] {name}: losses {losses}, wire "
            f"{float(metrics['wire_bytes']) / 1e6:.3f} MB/round, "
            f"{steps} steps {time.perf_counter() - t0:.3f}s (host clock)")
        require(np.all(np.isfinite(losses)), f"{name} losses {losses}")
        runs[name] = (losses, state.x, text)

    # the bit-packed wire must show in the program: the wire_pack kernels
    # on top of the dense ring's, and far fewer bytes permuted (top_k keeps
    # FRAC of the values; the dense wire ships every bf16 element)
    ring_text, packed_text = runs["ring"][2], runs["ring+packed_bits"][2]
    kernels = {n: runs[n][2].count("tpu_custom_call") for n in runs}
    sent = {n: permute_bytes(runs[n][2]) for n in ("ring", "ring+packed_bits")}
    log(f"[4] packed_bits vs dense-wire ring: {kernels['ring+packed_bits']} "
        f"vs {kernels['ring']} tpu_custom_call, collective-permute bytes "
        f"{sent['ring+packed_bits']} vs {sent['ring']}")
    require(interpret or kernels["ring+packed_bits"] > kernels["ring"],
            "packed_bits ring: no wire_pack kernel beyond the dense ring's")
    require(any(("u16[" in l or "u32[" in l)
                for l in packed_text.splitlines()
                if "collective-permute" in l),
            "packed_bits ring ships no u16/u32 collective-permute")
    require(0 < sent["ring+packed_bits"] <= PACKED_BYTES_MAX * sent["ring"],
            f"packed_bits ring permutes {sent['ring+packed_bits']} bytes, "
            f"not under {PACKED_BYTES_MAX} of the dense ring's "
            f"{sent['ring']}")
    (l_ring, x_ring, _), (l_dense, x_dense, _) = runs["ring"], runs["dense"]
    loss_gap = float(np.max(np.abs(np.subtract(l_ring, l_dense))
                            / np.abs(l_dense)))

    @jax.jit
    def rel_gap(a, b):
        num = sum(jnp.sum((p - r) ** 2) for p, r in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
        den = sum(jnp.sum(r ** 2) for r in jax.tree_util.tree_leaves(b))
        return jnp.sqrt(num / den)
    x_gap = float(rel_gap(x_ring, x_dense))
    log(f"[4] ring vs dense gossip: max relative loss gap {loss_gap:.3e} "
        f"(limit {RING_LOSS_RTOL}), relative gap of final x "
        f"{x_gap:.3e} (limit {RING_X_RTOL}); the limits cover one mix "
        "summed in another order")
    require(loss_gap <= RING_LOSS_RTOL and x_gap <= RING_X_RTOL,
            "ring and dense gossip disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    from repro._env import use_compile_cache
    log(f"[env] compile cache {use_compile_cache()}")
    t_start = time.perf_counter()
    device = phase_device()
    if args.chips == 4:
        if device["count"] < 4:
            raise SystemExit(f"chip_smoke --chips 4: {device['count']} "
                             "chips found")
        phase_agent_ring(_config())
    else:
        phase_comm_round(_config())
        phase_train()
    log(f"[d] total {time.perf_counter() - t_start:.1f}s (host clock)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
