"""End-to-end decentralized training driver for *any* registered algorithm.

``--algo`` picks an entry from the algorithm registry (porter-gc, porter-dp,
beer, porter-adam, dsgd, choco, dp-sgd, soteriafl); the driver builds it
through the ``repro.api`` facade, so topology/compressor/engine construction
and the gamma derivation live in one place.  Runs for real on whatever
devices exist -- the CPU container trains reduced configs; on a TPU pod the
same driver shards over the production mesh (the step builder is shared
with the dry-run).

Training runs through the chunked runtime (``repro.launch.runtime``):
``--chunk N`` scan-fuses N comm rounds into one compiled dispatch with
donated state and on-device batch synthesis (``repro.data.batch_source``),
so the host syncs once per chunk instead of once per round.  Logging,
checkpointing and divergence gating happen at chunk boundaries; the
trajectory is chunking-invariant (same key stream per round), so ``--chunk
8`` reproduces ``--chunk 1``.  Checkpoints record cumulative executed
rounds and the calibrated sigma in their manifest, so a ``--resume`` run
advances the privacy accountant only by rounds actually spent and never
re-calibrates noise mid-stream.

Examples (CPU, ~100M-scale and smoke-scale):
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 50 --batch 8 --seq 128 --chunk 8
    PYTHONPATH=src python -m repro.launch.train --smoke --algo choco
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-7b --smoke \
        --algo porter-dp --epsilon 0.1 --steps 30

On one TPU v5e chip, at published widths cut to one layer (the run
chip_smoke.py makes; the comm round then uses the compiled Pallas kernels):
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --n-layers 1 --agents 2 --batch 2 --seq 512 --plane-dtype bf16 \
        --steps 4 --chunk 2
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.api import (VARIANT_TO_ALGO, ExperimentSpec, algorithm_info,
                       build, list_algorithms)
from repro.configs import get_config, get_smoke
from repro.core import MomentsAccountant, calibrate_sigma, ldp_epsilon
from repro.core.comm_round import resolve_backend
from repro.data import batch_source
from repro.launch.runtime import run_chunked
from repro.models import build_model


def resolve_privacy(info, args, start: int, manifest_extra: dict):
    """(sigma_p, accountant, rounds_prev) honoring rounds already spent.

    Fresh DP run: Theorem-1 calibration of sigma for the ``--steps``
    horizon.  Resume: sigma comes from the checkpoint manifest (the rounds
    already executed were perturbed with *that* sigma -- re-calibrating as
    if no rounds were spent would silently mis-state the guarantee), and
    the moments accountant is advanced by the manifest's cumulative
    ``rounds_executed`` before a single new round runs.
    """
    rounds_prev = int(manifest_extra.get("rounds_executed", start))
    if not info.dp:
        return 0.0, None, rounds_prev
    sigma_saved = manifest_extra.get("sigma_p")
    if start > 0 and sigma_saved:
        # the accountant describes the mechanism that actually ran: the
        # manifest's tau / local_samples govern it, and changing them on
        # resume would mix rounds clipped/noised under different regimes
        # -- refuse rather than silently mis-state the guarantee
        for knob, arg_val in (("tau", args.tau),
                              ("local_samples", args.local_samples)):
            saved = manifest_extra.get(knob)
            if saved is not None and saved != arg_val:
                raise ValueError(
                    f"--resume with --{knob.replace('_', '-')}={arg_val} "
                    f"but the checkpoint's {rounds_prev} rounds ran with "
                    f"{knob}={saved}; resume with the recorded value (the "
                    "noise was calibrated to it)")
        sigma_p = float(sigma_saved)
        acct = MomentsAccountant(q=1.0 / args.local_samples,
                                 noise_multiplier=sigma_p / args.tau)
        acct.step(rounds_prev)
        print(f"[privacy] resumed: sigma_p={sigma_p:.4g} from the manifest; "
              f"{rounds_prev} rounds already spent, accountant eps so far="
              f"{acct.epsilon(args.delta):.4g}")
    else:
        if start > 0:
            # a DP checkpoint without sigma_p metadata predates the
            # accounting manifest: the spent rounds' noise scale is
            # unknown, so any eps we print would be fiction -- refuse
            # instead of silently re-calibrating over them
            raise ValueError(
                f"--resume of a DP run, but the checkpoint manifest "
                f"records no sigma_p for the {rounds_prev} rounds already "
                "spent (pre-runtime checkpoint?); restart fresh or re-save "
                "the checkpoint with privacy metadata")
        sigma_p = calibrate_sigma(args.tau, args.steps, args.local_samples,
                                  args.epsilon, args.delta)
        acct = MomentsAccountant(q=1.0 / args.local_samples,
                                 noise_multiplier=sigma_p / args.tau)
        acct.step(rounds_prev)
        eps_plan = ldp_epsilon(args.tau, sigma_p, args.steps,
                               args.local_samples, args.delta)
        print(f"[privacy] sigma_p={sigma_p:.4g} for "
              f"({args.epsilon},{args.delta})-LDP over {args.steps} steps; "
              f"accountant eps={eps_plan:.4g}")
    return sigma_p, acct, rounds_prev


def model_config(arch: str, smoke: bool = False, n_layers=None):
    """The config the trainer runs: published (or ``--smoke``), remat off,
    and with ``n_layers`` the depth cut to that many layers -- the only
    field the cut changes."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    cfg = dataclasses.replace(cfg, remat=False)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth to N layers; every width stays as "
                         "the config publishes it")
    ap.add_argument("--algo", default=None, choices=list(list_algorithms()),
                    help="registered algorithm (default porter-gc; "
                         "see repro.api)")
    ap.add_argument("--variant", default=None,
                    choices=sorted(VARIANT_TO_ALGO),
                    help="deprecated alias for --algo (gc/dp/beer -> "
                         "porter-*, csgp -> dp-csgp)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--chunk", type=int, default=1,
                    help="comm rounds scan-fused per dispatch (donated "
                         "state, on-device batches); logging/checkpoint/"
                         "divergence gating happen at chunk boundaries")
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-agent batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--topology-schedule", default=None,
                    help="time-varying topology generator spec (e.g. "
                         "'dropout:rate=0.2,period=8', "
                         "'rotate:ring+star+complete', "
                         "'erdos_renyi:period=8'); round t mixes with "
                         "W_{t mod period}, indexed inside the compiled "
                         "chunk by the state's step counter")
    ap.add_argument("--compressor", default="top_k")
    ap.add_argument("--frac", type=float, default=0.05)
    ap.add_argument("--fleet", action="store_true",
                    help="vectorized fleet mode (n >> devices): one "
                         "leading agent axis, dense/COO mixing sweep "
                         "(see core/fleet.py; forces dense gossip/wire)")
    ap.add_argument("--plane-dtype", default=None, choices=["f32", "bf16"],
                    help="EF/gossip state plane dtype (bf16 halves resident "
                         "state + dense wire; f32 master params, stochastic-"
                         "rounding writeback). Default: derive from params")
    ap.add_argument("--remat-policy", default=None,
                    choices=["full", "dots"],
                    help="jax.checkpoint around the loss/grad ('full' "
                         "rematerializes everything, 'dots' saves matmul "
                         "outputs); default off")
    ap.add_argument("--eta", type=float, default=3e-2)
    ap.add_argument("--tau", type=float, default=1.0)
    ap.add_argument("--epsilon", type=float, default=0.1,
                    help="LDP epsilon target (DP algorithms)")
    ap.add_argument("--delta", type=float, default=1e-3)
    ap.add_argument("--local-samples", type=int, default=4096,
                    help="m: per-agent dataset size (privacy accounting)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.algo and args.variant:
        ap.error("--algo and --variant are mutually exclusive")
    if args.chunk < 1:
        ap.error("--chunk must be >= 1")
    if args.n_layers is not None and args.n_layers < 1:
        ap.error("--n-layers must be >= 1")
    algo_name = (args.algo or
                 (VARIANT_TO_ALGO[args.variant] if args.variant
                  else "porter-gc"))
    info = algorithm_info(algo_name)

    cfg = model_config(args.arch, args.smoke, args.n_layers)
    bundle = build_model(cfg)

    # probe the checkpoint before calibrating: resume must keep the sigma
    # the spent rounds were perturbed with, and the accountant must start
    # from the manifest's cumulative round count
    start, manifest_extra = 0, {}
    if args.resume and args.ckpt_dir:
        from repro.launch.checkpoint import latest_step, read_manifest
        if latest_step(args.ckpt_dir) is not None:
            start = int(latest_step(args.ckpt_dir))
            manifest_extra = read_manifest(args.ckpt_dir).get("extra", {})
    sigma_p, acct, rounds_prev = resolve_privacy(info, args, start,
                                                 manifest_extra)

    # a schedule is part of the trajectory: round t's W_t is indexed by the
    # restored step counter, so resuming under a *different* schedule would
    # silently splice two topologies into one run -- refuse, like tau
    saved_sched = manifest_extra.get("topology_schedule")
    if start > 0 and saved_sched != args.topology_schedule:
        raise ValueError(
            f"--resume with --topology-schedule={args.topology_schedule!r} "
            f"but the checkpoint's {rounds_prev} rounds ran with "
            f"{saved_sched!r}; resume with the recorded schedule (the step "
            "counter continues its period mid-window)")

    # plane dtype is part of the state layout: the checkpoint's buffers ARE
    # that dtype, and restoring them into a different layout would silently
    # re-round (bf16 -> f32 resurrects no precision, f32 -> bf16 drops it
    # outside the SR path) -- refuse, like the schedule
    saved_planes = manifest_extra.get("plane_dtype")
    if start > 0 and saved_planes != args.plane_dtype:
        raise ValueError(
            f"--resume with --plane-dtype={args.plane_dtype!r} but the "
            f"checkpoint's {rounds_prev} rounds ran with "
            f"{saved_planes!r}; resume with the recorded plane dtype")

    spec = ExperimentSpec(algo=algo_name, n_agents=args.agents,
                          topology=args.topology,
                          topology_schedule=args.topology_schedule,
                          compressor=args.compressor, frac=args.frac,
                          plane_dtype=args.plane_dtype,
                          remat_policy=args.remat_policy,
                          eta=args.eta, tau=args.tau, sigma_p=sigma_p,
                          fleet=args.fleet)
    algo = build(spec, bundle.loss)

    params, _ = bundle.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    if algo.schedule is not None:
        s = algo.schedule
        top_note = (f"{s.kind}, period={s.period}, "
                    f"joint gap={s.joint_spectral_gap:.3f}, "
                    f"per-round alpha={s.alpha:.3f}")
    elif algo.topology is not None:
        top_note = f"{args.topology}, alpha={algo.topology.alpha:.3f}"
    else:
        top_note = "server/client"
    mp_note = "".join(
        [f" planes={args.plane_dtype}" if args.plane_dtype else "",
         f" remat={args.remat_policy}" if args.remat_policy else ""])
    devices = jax.devices()
    print(f"[model] {cfg.name}: {cfg.n_layers} layers, "
          f"{n_params/1e6:.2f}M params, "
          f"{args.agents} agents ({top_note}), "
          f"{args.compressor}(rho={args.frac}) algo={algo_name} "
          f"chunk={args.chunk}{mp_note} on {devices[0].platform} "
          f"{devices[0].device_kind} x{len(devices)} "
          f"comm={resolve_backend(spec.comm_backend)}")

    state = algo.init(params)
    del params  # the state holds its own copy; free the device buffers
    if start > 0:
        from repro.launch.checkpoint import restore_state
        state = restore_state(args.ckpt_dir, like=state)
        print(f"[ckpt] resumed from step {start}")
        if start >= args.steps:
            print(f"[done] checkpoint already at step {start} >= "
                  f"--steps {args.steps}; nothing to train")
            if args.out:  # downstream readers still expect the file
                Path(args.out).parent.mkdir(parents=True, exist_ok=True)
                Path(args.out).write_text(json.dumps([]))
            return 0

    source = batch_source(cfg, args.agents, args.batch, args.seq)
    history = []
    run = {"t": start, "diverged": False}
    t0 = time.time()

    def ckpt_extra(t_end: int) -> dict:
        extra = {"rounds_executed": rounds_prev + (t_end - start)}
        if args.topology_schedule is not None:
            extra["topology_schedule"] = args.topology_schedule
        if args.plane_dtype is not None:
            extra["plane_dtype"] = args.plane_dtype
        if info.dp:
            extra.update(sigma_p=sigma_p, tau=args.tau,
                         epsilon=args.epsilon, delta=args.delta,
                         local_samples=args.local_samples)
        return extra

    def on_chunk(t_start, t_end, st, metrics):
        # one host sync per chunk: the stacked metrics come down together
        m_host = jax.device_get(metrics)
        wall = round(time.time() - t0, 2)
        for i, t in enumerate(range(t_start, t_end)):
            if t % args.log_every == 0 or t == args.steps - 1:
                m = {k: float(v[i]) for k, v in m_host.items()}
                m["step"] = t
                m["wall_s"] = wall
                history.append(m)
                extra = "".join(
                    f"  {label} {m[k]:.3e}" for k, label in
                    (("consensus_x", "consensus_x"), ("v_norm", "|v|"))
                    if k in m)
                print(f"  step {t:5d}  loss {m['loss']:.4f}{extra}  "
                      f"wire {m['wire_bytes']/1e6:.3f}MB/round  "
                      f"({m['wall_s']}s)")
        run["t"] = t_end
        if not np.isfinite(m_host["loss"][-1]):
            # gate BEFORE checkpointing: the last good checkpoint must
            # survive so --resume can recover from it
            run["diverged"] = True
            print(f"[diverged] non-finite loss at step {t_end - 1}; "
                  "stopping")
            return False
        if args.ckpt_dir and \
                t_end // args.ckpt_every > t_start // args.ckpt_every:
            from repro.launch.checkpoint import save_state
            save_state(args.ckpt_dir, st, step=t_end,
                       extra=ckpt_extra(t_end))

    run_chunked(algo, source, state, jax.random.PRNGKey(1), args.steps,
                chunk=args.chunk, start=start, on_chunk=on_chunk)

    executed = run["t"] - start
    if acct is not None:
        acct.step(executed)
        print(f"[privacy] executed {executed} rounds this run "
              f"({rounds_prev + executed} cumulative); accountant "
              f"eps={acct.epsilon(args.delta):.4g} at delta={args.delta:g}")
    if args.out:  # written even on divergence: downstream readers expect it
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(history, indent=2))
    if run["diverged"] or not history:
        return 1
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"[done] loss {first:.4f} -> {last:.4f} in {executed} steps "
          f"({time.time()-t0:.1f}s)")
    # Exit gate: fail on divergence, not on noise.  The smoke task is random
    # tokens (loss sits at its entropy floor and fluctuates), and DP runs
    # are perturbation-dominated, so require descent *or* staying within a
    # small band of the initial loss; NaN/blow-up still exits nonzero.
    ok = np.isfinite(last) and (last < first
                                or abs(last - first) <= 0.02 * abs(first))
    return 0 if (ok or (info.dp and np.isfinite(last))) else 1


if __name__ == "__main__":
    from repro._env import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
