"""Repo-wide invariant sweep: every registered algorithm x executor x wire.

Drives the four passes in :mod:`repro.analysis.hlo` over a tiny stock
problem (d = 2*PACK_BLOCK so the packed wire formats get real windows) on
a CPU host mesh, so ``python -m repro.analysis --all`` proves -- without
running a training step -- that:

* each compiled step ships no more collectives than its gossip executor's
  declared :class:`~repro.core.gossip.GossipBudget` times the algorithm's
  registered ``comm_rounds`` (and *zero* for the centralized algorithms);
* under ``wire='packed_bits'`` only bf16/u16/u32 buffers cross the wire
  (f32 capped at the codec's declared per-window overhead);
* every algorithm's chunk runner donates all carried state leaves and
  never retraces across a schedule period.

The harness deliberately mirrors the repo's own test idiom (the
test_wire_pack / test_runtime problem shapes), so a budget violation here
reproduces in one of those tests' terms.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.api as api
from repro.api import ExperimentSpec, build
from repro.core import FLEET_DENSE_GATE
from repro.core import wire_formats as WF
from repro.core.registry import algorithm_info, list_algorithms
from repro.data import minibatch_source
from repro.launch.mesh import make_mesh

from . import hlo as H

__all__ = [
    "Case",
    "census_matrix",
    "run_census_case",
    "probe_algorithm",
    "run_all",
    "repo_root",
    "make_agent_mesh",
]

# census problem: big enough for two real PACK_BLOCK windows per leaf
N_AGENTS = 4
D_CENSUS = 2 * WF.PACK_BLOCK

# probe problem (donation / retrace): the chunked-runtime test shape
D_PROBE, M_PROBE, B_PROBE = 16, 32, 3

# schedule specs used to prove traced-W_t invariance (period 3 each)
CHURN_SCHEDULE = "dropout:rate=0.25,period=3"
DIRECTED_SCHEDULE = "directed:one_way,rate=0.2,period=3"


def repo_root() -> Path:
    """<repo>/src/repro/api.py -> <repo>."""
    return Path(api.__file__).resolve().parents[2]


def make_agent_mesh(n: int = N_AGENTS) -> Mesh:
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"census needs {n} devices for the agent mesh, have "
            f"{len(devs)} -- run via `python -m repro.analysis` (it forces "
            "host devices before jax init) or set "
            "--xla_force_host_platform_device_count")
    return make_mesh((n,), ("data",), devices=devs[:n])


def census_loss(p, b):
    return jnp.mean((p["w"] - b) ** 2)


@dataclasses.dataclass(frozen=True)
class Case:
    label: str
    spec: ExperimentSpec
    needs_mesh: bool


def _spec_for(algo: str, **kw) -> ExperimentSpec:
    base = dict(algo=algo, n_agents=N_AGENTS, topology="ring",
                topology_weights="metropolis", compressor="block_top_k",
                frac=0.25, comm_backend="ref", interpret=True, eta=0.1)
    if algorithm_info(algo).dp:
        base.update(tau=5.0, sigma_p=0.01)
    base.update(kw)
    return ExperimentSpec(**base)


def census_matrix(quick: bool = False) -> List[Case]:
    """Every registered algorithm x {dense, ring, packed} x {f32,
    packed_bits} x {static, scheduled}, minus invalid combos (dense gossip
    has no packed form; uncompressed/centralized algorithms have no codec;
    directed schedules are push-sum-only)."""
    engine_algos = [a for a in list_algorithms()
                    if (i := algorithm_info(a)).decentralized
                    and i.compressed and a != "dp-csgp"]
    central = [a for a in list_algorithms()
               if not algorithm_info(a).decentralized]
    if quick:
        engine_algos = ["porter-gc"]
        central = central[:1]

    cases: List[Case] = []
    for a in engine_algos:
        cases += [
            Case(f"{a}/dense/f32", _spec_for(a, gossip_mode="dense"), False),
            Case(f"{a}/ring/f32", _spec_for(a, gossip_mode="ring"), True),
            Case(f"{a}/packed/f32", _spec_for(a, gossip_mode="packed"),
                 True),
            Case(f"{a}/ring/packed_bits",
                 _spec_for(a, gossip_mode="ring", wire="packed_bits"), True),
            Case(f"{a}/packed/packed_bits",
                 _spec_for(a, gossip_mode="packed", wire="packed_bits"),
                 True),
        ]
    if not quick:
        cases += [
            Case("dsgd/dense/f32", _spec_for("dsgd", gossip_mode="dense"),
                 False),
            Case("dsgd/ring/f32", _spec_for("dsgd", gossip_mode="ring"),
                 True),
            Case("dsgd/packed/f32", _spec_for("dsgd", gossip_mode="packed"),
                 True),
        ]
    for a in central:
        cases.append(Case(f"{a}/none/f32", _spec_for(a), False))

    # directed (column-stochastic) schedules ride push-sum only
    cases.append(
        Case("dp-csgp/ring/packed_bits/directed",
             _spec_for("dp-csgp", gossip_mode="ring", wire="packed_bits",
                       topology_schedule="directed:ring_skips"), True))
    if not quick:
        cases += [
            Case("dp-csgp/dense/f32/directed",
                 _spec_for("dp-csgp", gossip_mode="dense",
                           topology_schedule=DIRECTED_SCHEDULE), False),
            Case("dp-csgp/packed/packed_bits/directed",
                 _spec_for("dp-csgp", gossip_mode="packed",
                           wire="packed_bits",
                           topology_schedule=DIRECTED_SCHEDULE), True),
            # traced-W_t schedules must not change the census
            Case("porter-gc/ring/packed_bits/rotate",
                 _spec_for("porter-gc", gossip_mode="ring",
                           wire="packed_bits",
                           topology_schedule=
                           "rotate:ring/metropolis+ring/lazy"), True),
            Case("porter-gc/packed/f32/churn",
                 _spec_for("porter-gc", gossip_mode="packed",
                           topology_schedule=CHURN_SCHEDULE), True),
            Case("porter-gc/ring/packed_bits/qsgd",
                 _spec_for("porter-gc", gossip_mode="ring",
                           wire="packed_bits", compressor="qsgd",
                           compressor_kwargs={"levels": 16}), True),
        ]
    # qsgd packed: the u32-word + f32-scale dtype-flow corner
    cases.append(
        Case("porter-gc/packed/packed_bits/qsgd",
             _spec_for("porter-gc", gossip_mode="packed",
                       wire="packed_bits", compressor="qsgd",
                       compressor_kwargs={"levels": 16}), True))
    # mixed-precision planes: with plane_dtype='bf16' the gossip
    # collectives themselves must ship <= 2 B/elem (dtype flow runs on
    # these even without a packed-bits codec -- see run_census_case);
    # the push-sum case additionally proves the f32-exact weight rider
    # stays a bounded scalar, not a hidden dense upcast.
    cases.append(
        Case("porter-gc/ring/f32/bf16planes",
             _spec_for("porter-gc", gossip_mode="ring",
                       plane_dtype="bf16"), True))
    if not quick:
        cases += [
            Case("porter-gc/packed/f32/bf16planes",
                 _spec_for("porter-gc", gossip_mode="packed",
                           plane_dtype="bf16"), True),
            Case("porter-gc/ring/packed_bits/bf16planes",
                 _spec_for("porter-gc", gossip_mode="ring",
                           wire="packed_bits", plane_dtype="bf16"), True),
            Case("dp-csgp/ring/f32/bf16planes/directed",
                 _spec_for("dp-csgp", gossip_mode="ring",
                           plane_dtype="bf16",
                           topology_schedule="directed:ring_skips"), True),
        ]
    # fleet mode: the whole mixing sweep is device-local math (schedule
    # einsum below FLEET_DENSE_GATE, COO scatter-add above), so the
    # unmeshed census must count ZERO collectives -- the fleet budget
    # declares an empty per_leaf table, making every category unbudgeted
    cases.append(Case("porter-gc/fleet/dense",
                      _spec_for("porter-gc", gossip_mode="dense",
                                fleet=True), False))
    if not quick:
        cases += [
            Case("clip21/fleet/dense",
                 _spec_for("clip21", gossip_mode="dense", fleet=True),
                 False),
            Case("subgrad-comp/fleet/coo",
                 _spec_for("subgrad-comp", gossip_mode="dense", fleet=True,
                           n_agents=2 * FLEET_DENSE_GATE), False),
        ]
    return cases


def _agent_shardings(mesh: Mesh, tree, n: int):
    """Leading-axis-``n`` leaves shard over 'data'; the rest replicate."""
    def spec(l):
        if getattr(l, "ndim", 0) >= 1 and l.shape[0] == n:
            return NamedSharding(mesh, P("data", *([None] * (l.ndim - 1))))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(spec, tree)


def lowered_step_text(algo, *, mesh: Optional[Mesh], n: int = N_AGENTS,
                      d: int = D_CENSUS) -> str:
    """Compile ``algo.step`` on the stock census problem; return its
    optimized HLO."""
    params0 = {"w": jnp.zeros(d)}
    state = algo.init(params0)
    batch = jnp.zeros((n, 1, d))
    key = jax.random.PRNGKey(0)
    if mesh is not None:
        state = jax.device_put(state, _agent_shardings(mesh, state, n))
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None,
                                                            None)))
        key = jax.device_put(key, NamedSharding(mesh, P()))
    return jax.jit(algo.step).lower(state, batch, key).compile().as_text()


def run_census_case(case: Case, mesh: Optional[Mesh]) -> dict:
    """Lower one spec and run the census (+ dtype flow for packed wires)."""
    rec = {"label": case.label, "algo": case.spec.algo,
           "gossip": case.spec.gossip_mode, "wire": case.spec.wire,
           "schedule": case.spec.topology_schedule, "ok": False}
    use_mesh = mesh if case.needs_mesh else None
    try:
        algo = build(case.spec, census_loss, mesh=use_mesh)
        hlo_text = lowered_step_text(algo, mesh=use_mesh,
                                     n=case.spec.n_agents)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    info = algorithm_info(case.spec.algo)
    budget = (getattr(algo.mixer, "budget", None) if algo.mixer is not None
              else H.NO_GOSSIP_BUDGET)
    n_leaves = 1  # the census problem gossips a single {'w'} leaf
    census = H.check_census(
        hlo_text, budget=budget, n_leaves=n_leaves,
        comm_rounds=info.comm_rounds, meshed=use_mesh is not None)
    rec["census"] = census.to_json()
    ok = census.ok

    if case.spec.wire == "packed_bits":
        codec = algo.engine.mixer.wire_codec
        allowance = (info.comm_rounds * N_AGENTS * n_leaves
                     * codec.overhead_bytes(D_CENSUS) + 64)
        flow = H.check_dtype_flow(hlo_text,
                                  f32_allowance_bytes=allowance)
        rec["dtype_flow"] = flow.to_json()
        ok = ok and flow.ok
    elif case.spec.plane_dtype is not None:
        # bf16 state planes without a packed-bits codec: the plane wire is
        # the collectives themselves, so the same <=2 B/elem contract
        # applies directly.  The f32 allowance covers only scalar riders
        # (push-sum weight words, traced band weights) -- one leaked dense
        # f32 plane is 4*D_CENSUS = 8 KiB and trips it immediately.
        flow = H.check_dtype_flow(hlo_text, f32_allowance_bytes=1024)
        rec["dtype_flow"] = flow.to_json()
        ok = ok and flow.ok
    rec["ok"] = ok
    return rec


# ---------------------------------------------------------------------------
# Donation + retrace probes (mesh-free; the chunked-runtime problem).
# ---------------------------------------------------------------------------

def probe_loss(params, batch):
    f, l = batch
    f, l = jnp.atleast_2d(f), jnp.atleast_1d(l)
    logits = f @ params["w"] + params["b"]
    return jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))


def probe_problem(seed: int = 0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=D_PROBE)
    f = rng.normal(size=(N_AGENTS, M_PROBE, D_PROBE)).astype(np.float32)
    l = (f @ w_true > 0).astype(np.float32)
    params0 = {"w": jnp.zeros(D_PROBE), "b": jnp.zeros(())}
    return params0, minibatch_source(f, l, B_PROBE)


def probe_algorithm(name: str) -> dict:
    """Donation + schedule-period retrace for one algorithm (dense gossip;
    the runner contract is executor-independent)."""
    rec = {"algo": name, "ok": False}
    info = algorithm_info(name)
    params0, source = probe_problem()
    try:
        algo = build(_spec_for(name, n_agents=N_AGENTS,
                               gossip_mode="dense"), probe_loss)
        donation = H.check_donation(algo, source, params0, chunk=2)
        rec["donation"] = donation.to_json()

        if info.decentralized:
            sched = (DIRECTED_SCHEDULE if name == "dp-csgp"
                     else CHURN_SCHEDULE)
            algo_s = build(_spec_for(name, n_agents=N_AGENTS,
                                     gossip_mode="dense",
                                     topology_schedule=sched), probe_loss)
            retrace = H.check_retrace(algo_s, source, params0,
                                      chunks=(2, 3), period=3)
            rec["schedule"] = sched
        else:
            retrace = H.check_retrace(algo, source, params0,
                                      chunks=(2, 3), period=1)
        rec["retrace"] = retrace.to_json()
        rec["ok"] = donation.ok and retrace.ok
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


# ---------------------------------------------------------------------------
# Top-level driver.
# ---------------------------------------------------------------------------

def run_all(*, quick: bool = False, mesh: Optional[Mesh] = None,
            do_census: bool = True, do_probes: bool = True,
            do_lint: bool = True, do_tables: bool = True,
            algos: Optional[Sequence[str]] = None,
            log=print) -> dict:
    """The ``--all`` sweep: census + probes + AST lint + table checks.

    Returns the machine-readable report dict; ``report['ok']`` aggregates.
    """
    from . import ast_rules

    report: dict = {"quick": quick}
    failures: List[str] = []

    if do_census:
        if mesh is None:
            mesh = make_agent_mesh()
        records = []
        cases = census_matrix(quick=quick)
        if algos:
            cases = [c for c in cases if c.spec.algo in set(algos)]
        for case in cases:
            rec = run_census_case(case, mesh)
            records.append(rec)
            status = "ok" if rec["ok"] else "FAIL"
            counts = rec.get("census", {}).get("counts", {})
            shown = {k: v for k, v in counts.items() if v} or {}
            log(f"[census {status}] {rec['label']:<42s} {shown}"
                + (f"  {rec.get('error', '')}" if not rec["ok"] else ""))
            if not rec["ok"]:
                failures.append(f"census:{rec['label']}")
        report["census"] = records

    if do_probes:
        probes = []
        names = list(algos) if algos else sorted(list_algorithms())
        if quick:
            names = names[:3]
        for name in names:
            rec = probe_algorithm(name)
            probes.append(rec)
            status = "ok" if rec["ok"] else "FAIL"
            log(f"[probe  {status}] {name:<42s} "
                f"donated={rec.get('donation', {}).get('aliased', '?')} "
                f"executables={rec.get('retrace', {}).get('executables')}"
                + (f"  {rec.get('error', '')}" if not rec["ok"] else ""))
            if not rec["ok"]:
                failures.append(f"probe:{name}")
        report["probes"] = probes

    if do_lint:
        root = repo_root()
        targets = [root / "src", root / "benchmarks", root / "examples"]
        findings = ast_rules.lint_paths([t for t in targets if t.exists()],
                                        root=root)
        for f in findings:
            log(f"[lint   FAIL] {f}")
            failures.append(f"lint:{f.path}:{f.line}")
        log(f"[lint] {len(findings)} finding(s) over "
            f"{', '.join(t.name for t in targets if t.exists())}")
        report["lint"] = [f.to_json() for f in findings]

    if do_tables:
        tfindings = ast_rules.check_tables()
        for f in tfindings:
            log(f"[tables FAIL] {f}")
            failures.append(f"tables:{f.path}")
        log(f"[tables] {len(tfindings)} drift(s)")
        report["tables"] = [f.to_json() for f in tfindings]

    report["failures"] = failures
    report["ok"] = not failures
    return report


def write_report(report: dict, out_path) -> Path:
    out_path = Path(out_path)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(report, indent=2))
    return out_path
