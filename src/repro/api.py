"""repro.api -- the one facade over every decentralized optimizer.

The paper analyzes PORTER-DP, PORTER-GC, BEER, CHOCO-SGD, DSGD, DP-SGD and
SoteriaFL-SGD in one framework; this module exposes them through one
framework too.  A declarative :class:`ExperimentSpec` names the algorithm
and its knobs (topology, compressor, gossip mode, clipping/privacy,
comm backend), and :func:`build` turns it into a ready-to-train
:class:`repro.core.registry.Algorithm`:

    from repro.api import ExperimentSpec, build

    spec = ExperimentSpec(algo="porter-gc", n_agents=10,
                          topology="erdos_renyi", topology_p=0.8,
                          compressor="top_k", frac=0.05, eta=0.05, tau=1.0)
    algo = build(spec, loss_fn)
    state = algo.init(params0)
    step = jax.jit(algo.step)
    state, metrics = step(state, batch, key)   # metrics: loss, wire_bytes, ...

``build`` owns everything that used to be copy-pasted at every call site:
topology + mixing-matrix construction, compressor construction, the
comm-round engine, and the paper's consensus-stepsize derivation

    gamma = gamma_scale * (1 - alpha) * rho        (default scale 1/2)

with ``alpha`` the mixing rate of the resolved topology and ``rho`` the
resolved compressor's contraction factor.  A ``topology_schedule`` spec
string swaps the static graph for a time-varying
:class:`repro.core.mixing.TopologySchedule` (churn, stragglers, graph
rotation, per-round ER resampling); ``alpha`` then becomes the schedule's
per-round geometric mixing rate, and the gossip executors index the
schedule table by the state's step counter inside the compiled program.  Launch-level hooks (mesh,
agent axes, shard-local compression, sharded leaf specs) are keyword
arguments of :func:`build` -- they are runtime objects, not experiment
declarations, so they stay out of the spec.

Registered algorithms (see :func:`repro.core.registry.list_algorithms`):

    porter-gc    Algorithm 1, Option II (batch-then-clip)
    porter-dp    Algorithm 1, Option I  (per-sample clip + Gaussian noise)
    beer         the unclipped ancestor [ZLL+22] (tau pinned to inf)
    porter-adam  beyond-paper: Adam-preconditioned tracked gradient
    dsgd         decentralized SGD with uncompressed gossip
    choco        CHOCO-SGD [KSJ19], compressed gossip, no tracking
    dp-sgd       centralized DP-SGD [ACG+16] (Table 1 reference point)
    soteriafl    SoteriaFL-SGD [LZLC22], server/client shifted compression
    dp-csgp      beyond-paper: DP compressed gossip over *directed* graphs
                 (column-stochastic W + push-sum de-biasing, arXiv
                 2512.13583); pair with topology_schedule="directed:..."
    clip21       beyond-paper: Clip21 error-feedback clipping (arXiv
                 2305.18929) -- clips the gradient *residual* against a
                 running estimate, so the clipping bias vanishes once the
                 iterates stabilize; bit-exact porter-gc at tau=inf
    subgrad-comp beyond-paper: nonsmooth subgradient method with
                 compressed gossip (arXiv 2607.01755 family) --
                 CHOCO's round with the 1/sqrt(t) diminishing stepsize

Fleet mode (``ExperimentSpec(fleet=True)``): the agent axis becomes a
simulated *fleet* of n = 1k-100k agents on however few devices exist --
same agent-stacked state layout, but mixing runs through
:func:`repro.core.fleet.make_fleet_mixer`: the identical dense einsum at
n <= FLEET_DENSE_GATE (bit-exact against the per-device engine, pinned
by tests/test_fleet.py) and a sparse COO scatter-add above it, where the
topology/schedule builders also switch to the sparse fleet generators so
no dense (n, n) table is ever materialized.

The per-algorithm functional APIs (``porter_step``, ``choco_step``, ...)
remain importable for tests and power users, but no call site should build
mixers/topologies/engines by hand anymore -- that is the facade's job.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import baselines as BL
from repro.core.beer import beer_config
from repro.core.clip21 import Clip21State, clip21_init, clip21_step
from repro.core.comm_round import CommRound, resolve_backend
from repro.core.compression import Compressor, make_compressor
from repro.core.fleet import (FLEET_DENSE_GATE, fleet_er_schedule,
                              fleet_rotating_schedule, fleet_topology,
                              make_fleet_mixer)
from repro.core import mixing as MX
from repro.core import wire_formats
from repro.core.gossip import MixFn, make_mixer
from repro.core.mixing import Topology, TopologySchedule, make_topology
from repro.core.porter import (PorterConfig, PorterState, porter_init,
                               porter_step)
from repro.core.subgrad import SubgradState, subgrad_init, subgrad_step
from repro.core.porter_adam import (PorterAdamState, porter_adam_init,
                                    porter_adam_step)
from repro.core.push_sum import DpCsgpState, dp_csgp_init, dp_csgp_step
from repro.core.registry import (Algorithm, AlgorithmInfo, algorithm_info,
                                 get_factory, list_algorithms,
                                 register_algorithm)

__all__ = [
    "ExperimentSpec",
    "VARIANT_TO_ALGO",
    "build",
    "build_engine",
    "resolve_topology",
    "resolve_schedule",
    "resolve_fleet_topology",
    "resolve_fleet_schedule",
    "resolve_compressor",
    "resolve_wire_format",
    "resolve_gamma",
    "resolve_plane_dtype",
    "Algorithm",
    "AlgorithmInfo",
    "algorithm_info",
    "list_algorithms",
]

# compressors whose knob is a kept-fraction (rho = frac)
_FRAC_COMPRESSORS = ("top_k", "block_top_k", "random_k")

# legacy PorterConfig.variant spelling -> registry name (launch drivers
# keep accepting --variant / variant= as sugar; one mapping, kept next to
# the registrations it must stay in sync with)
VARIANT_TO_ALGO = {"gc": "porter-gc", "dp": "porter-dp", "beer": "beer",
                   "csgp": "dp-csgp"}


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Declarative description of one decentralized-training experiment.

    Every field is a plain value (names, floats, bools) so specs can be
    logged, swept and diffed; :func:`build` resolves them into objects.
    ``gamma=None`` means "derive it": gamma_scale * (1 - alpha) * rho
    (the paper's stable choice) for compressed gossip, 1.0 for plain DSGD.
    ``tau=None`` disables clipping where that is optional (dsgd, choco,
    porter-gc/beer); the DP algorithms (porter-dp, dp-sgd, soteriafl)
    *reject* it -- their noise is calibrated to tau's sensitivity, so an
    unclipped run would silently void the privacy guarantee.
    """

    algo: str = "porter-gc"
    # agents + communication graph (Definition 1)
    n_agents: int = 10
    # fleet mode: simulate n_agents as a vectorized fleet (n >> devices).
    # The state layout is unchanged (leading agent axis, vmapped gradients,
    # shardable over devices); mixing routes through the fleet mixer --
    # bit-exact dense einsum at n <= repro.core.fleet.FLEET_DENSE_GATE,
    # sparse COO scatter-add above it (topology kinds ring / exponential /
    # erdos_renyi; schedules rotate / erdos_renyi).  Needs the default
    # dense gossip_mode and wire.
    fleet: bool = False
    topology: str = "ring"
    topology_weights: str = "metropolis"
    topology_p: float = 0.8          # erdos_renyi edge probability
    topology_seed: int = 0
    # time-varying topology (None = the static graph above).  A generator
    # spec string, resolved by resolve_schedule into a
    # repro.core.mixing.TopologySchedule whose (period, n, n) table the
    # gossip executors index with the traced round counter:
    #   "static"                              period-1 wrapper (parity tests)
    #   "rotate:ring+star+complete"           one graph kind per round
    #   "rotate:ring/metropolis+ring/lazy"    per-round weight schemes
    #   "rotate:ring+star,weights=lazy"       bare kinds + key=value knobs
    #   "erdos_renyi:period=8,p=0.6"          fresh connected ER every round
    #   "dropout:rate=0.2,period=8"           agent churn (offline w.p. rate)
    #   "straggler:rate=0.3,period=8"         per-link deadline misses
    #   "directed:ring_skips,skip=2"          COLUMN-stochastic: directed
    #   "directed:digraph,p=0.5,period=8"     ring w/ chords, random digraph,
    #   "directed:one_way,rate=0.2,period=8"  one-way link loss (push-sum
    #                                         algorithms only, e.g. dp-csgp)
    # Unset keys default to the topology_* fields above; the consensus
    # stepsize derivation then uses the schedule's joint spectral gap
    # (joint contraction factor for the directed family).
    # Server/client algorithms (dp-sgd, soteriafl) have no graph and
    # ignore it.
    topology_schedule: Optional[str] = None
    # compression (Definition 3)
    compressor: str = "top_k"
    frac: float = 0.05               # kept fraction for the sparse family
    compressor_kwargs: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)        # extras, e.g. block=, rank=, bits=
    # wire format / engine backend
    gossip_mode: str = "dense"       # 'dense' | 'ring' | 'packed'
    # 'dense' ships the dense emulation; 'packed_bits' fuses compression
    # with bit-packing so only compact buffers cross the wire (bf16+u16
    # top-k segments, uint32 QSGD code words -- core.wire_formats).  Needs
    # gossip_mode 'ring'/'packed' and a top_k/block_top_k/qsgd compressor.
    wire: str = "dense"              # 'dense' | 'packed_bits'
    # issue both PORTER comm rounds before either fused update, so the
    # collectives overlap the other round's local compute; bit-exact to the
    # sequential order (CommRound.overlap).  Single-round algos ignore it.
    overlap: bool = False
    comm_backend: str = "auto"       # 'auto' | 'pallas' | 'ref'
    interpret: Optional[bool] = None
    # stepsizes
    eta: float = 0.05
    gamma: Optional[float] = None    # None -> derived (see resolve_gamma)
    gamma_scale: float = 0.5
    # clipping / privacy (Definition 2 / Theorem 1)
    tau: Optional[float] = 1.0
    clip_mode: str = "smooth"
    sigma_p: float = 0.0
    dp: bool = False                 # per-sample clip+noise oracle for dsgd
    # porter-adam moments
    b1: float = 0.9
    b2: float = 0.999
    adam_eps: float = 1e-8
    # soteriafl shift stepsize
    alpha_shift: float = 0.5
    # EF/tracking buffer accumulation dtype
    buffer_dtype: Any = jnp.float32
    # storage dtype of the EF state planes: None = legacy f32 layout;
    # 'bf16' puts every parameter-sized EF buffer (q, m, v, g_prev, the
    # soteriafl shift) in bfloat16 -- resident optimizer state and the
    # gossip wire both drop to 2 B/element while the master params stay
    # f32 and the fused kernels keep f32 accumulation with a
    # stochastic-rounding writeback (kernels/sr_cast.py).  Accepts 'f32' /
    # 'bf16' strings or jnp dtypes (resolve_plane_dtype).
    plane_dtype: Any = None
    # rematerialization of the loss/grad inside algo.step: None = off,
    # 'full' = jax.checkpoint around the loss (recompute everything in the
    # backward pass), 'dots' = checkpoint with the dots_saveable policy
    # (keep matmul outputs, recompute the cheap elementwise rest) -- the
    # right knob for the models/ transformer+SSM stack on pod meshes.
    remat_policy: Optional[str] = None

    def replace(self, **kw) -> "ExperimentSpec":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class Resolved:
    """What :func:`build` constructed from a spec (the factory context)."""

    info: AlgorithmInfo
    topology: Optional[Topology]
    compressor: Optional[Compressor]
    mixer: Optional[MixFn]
    engine: Optional[CommRound]
    gamma: Optional[float]
    schedule: Optional[TopologySchedule] = None


# ---------------------------------------------------------------------------
# resolvers: spec fields -> objects (the construction no call site repeats)
# ---------------------------------------------------------------------------

def resolve_topology(spec: ExperimentSpec) -> Topology:
    return make_topology(spec.topology, spec.n_agents,
                         weights=spec.topology_weights, p=spec.topology_p,
                         seed=spec.topology_seed)


def _parse_schedule_kv(rest: str) -> Mapping[str, str]:
    kv = {}
    for item in filter(None, (s.strip() for s in rest.split(","))):
        k, sep, v = item.partition("=")
        if not sep:
            raise ValueError(
                f"bad schedule argument {item!r}: expected key=value "
                "(e.g. 'dropout:rate=0.2,period=8')")
        kv[k.strip()] = v.strip()
    return kv


def resolve_schedule(spec: ExperimentSpec,
                     topology: Optional[Topology] = None
                     ) -> Optional[TopologySchedule]:
    """Parse ``spec.topology_schedule`` into a TopologySchedule (or None).

    Unset generator knobs default to the spec's static-topology fields
    (weights, p, seed, and the base graph kind for churn generators);
    ``topology`` short-circuits the period-1 'static' wrapper so an
    externally supplied Topology override keeps parity."""
    if spec.topology_schedule is None:
        return None
    text = spec.topology_schedule
    kind, _, rest = text.partition(":")
    kind = kind.strip()
    if kind == "static":
        if rest.strip():
            raise ValueError(f"'static' schedule takes no arguments; got "
                             f"{text!r}")
        top = resolve_topology(spec) if topology is None else topology
        return MX.static_schedule(top)
    if kind == "directed":
        return _resolve_directed_schedule(spec, text, rest)
    allowed = {"rotate": {"kinds", "weights", "p", "seed"},
               "erdos_renyi": {"p", "period", "weights", "seed"},
               "dropout": {"rate", "period", "base", "weights", "p", "seed"},
               "straggler": {"rate", "period", "base", "weights", "p",
                             "seed"}}
    if kind not in allowed:
        raise ValueError(
            f"unknown topology schedule kind {kind!r} in {text!r}; have "
            "static, rotate, erdos_renyi, dropout, straggler, directed")
    if kind == "rotate" and rest:
        # the kinds list may lead bare: 'rotate:ring+star,weights=lazy'
        first, _, more = rest.partition(",")
        if "=" not in first:
            kv = {"kinds": first.strip(), **_parse_schedule_kv(more)}
        else:
            kv = dict(_parse_schedule_kv(rest))
    else:
        kv = dict(_parse_schedule_kv(rest))
    # reject typo'd keys BEFORE running a generator: the churn samplers do
    # real work (up to 1000 window-connectivity attempts)
    unknown = set(kv) - allowed[kind]
    if unknown:
        raise ValueError(f"unknown {kind!r} schedule keys {sorted(unknown)} "
                         f"in {text!r}; allowed: {sorted(allowed[kind])}")
    if kind == "rotate":
        kinds = [k for k in kv.pop("kinds", "").split("+") if k]
        if not kinds:
            raise ValueError("rotate schedule needs '+'-separated graph "
                             "kinds, e.g. 'rotate:ring+star+complete'")
        return MX.rotating_schedule(
            kinds, spec.n_agents,
            weights=kv.pop("weights", spec.topology_weights),
            p=float(kv.pop("p", spec.topology_p)),
            seed=int(kv.pop("seed", spec.topology_seed)))
    if kind == "erdos_renyi":
        return MX.erdos_renyi_schedule(
            spec.n_agents, p=float(kv.pop("p", spec.topology_p)),
            period=int(kv.pop("period", 8)),
            weights=kv.pop("weights", spec.topology_weights),
            seed=int(kv.pop("seed", spec.topology_seed)))
    gen = (MX.dropout_schedule if kind == "dropout"
           else MX.straggler_schedule)
    return gen(
        spec.n_agents, rate=float(kv.pop("rate", 0.2)),
        period=int(kv.pop("period", 8)),
        base=kv.pop("base", spec.topology),
        weights=kv.pop("weights", spec.topology_weights),
        p=float(kv.pop("p", spec.topology_p)),
        seed=int(kv.pop("seed", spec.topology_seed)))


def _resolve_directed_schedule(spec: ExperimentSpec, text: str,
                               rest: str) -> TopologySchedule:
    """'directed:<subkind>,key=value,...' -> a column-stochastic schedule.

    Subkinds (repro.core.mixing generators):
      ring_skips   static directed ring, optional skip chords   {skip}
      digraph      per-round random digraph                     {p, period,
                                                                 seed}
      one_way      directed churn: one-way link loss on the     {rate,
                   ring-with-skips base                          period,
                                                                 skip, seed}
    The leading subkind token may be bare (no '='), mirroring the rotate
    kinds list.  These tables are **column**-stochastic -- only push-sum
    algorithms (dp-csgp) de-bias them correctly; the doubly-stochastic
    family would silently drift toward the Perron vector.
    """
    first, _, more = rest.partition(",")
    sub = first.strip()
    if not sub or "=" in sub:
        raise ValueError(
            f"directed schedule needs a leading subkind in {text!r}, e.g. "
            "'directed:ring_skips,skip=2'; have ring_skips, digraph, "
            "one_way")
    allowed = {"ring_skips": {"skip"},
               "digraph": {"p", "period", "seed"},
               "one_way": {"rate", "period", "skip", "seed"}}
    if sub not in allowed:
        raise ValueError(
            f"unknown directed schedule subkind {sub!r} in {text!r}; have "
            f"{sorted(allowed)}")
    kv = dict(_parse_schedule_kv(more))
    unknown = set(kv) - allowed[sub]
    if unknown:
        raise ValueError(f"unknown directed:{sub} schedule keys "
                         f"{sorted(unknown)} in {text!r}; allowed: "
                         f"{sorted(allowed[sub])}")
    if sub == "ring_skips":
        return MX.directed_ring_schedule(spec.n_agents,
                                         skip=int(kv.pop("skip", 0)))
    if sub == "digraph":
        return MX.random_digraph_schedule(
            spec.n_agents, p=float(kv.pop("p", spec.topology_p)),
            period=int(kv.pop("period", 8)),
            seed=int(kv.pop("seed", spec.topology_seed)))
    return MX.directed_churn_schedule(
        spec.n_agents, rate=float(kv.pop("rate", 0.2)),
        period=int(kv.pop("period", 8)), skip=int(kv.pop("skip", 2)),
        seed=int(kv.pop("seed", spec.topology_seed)))


def resolve_compressor(spec: ExperimentSpec) -> Compressor:
    kwargs = dict(spec.compressor_kwargs)
    if spec.compressor in _FRAC_COMPRESSORS:
        kwargs.setdefault("frac", spec.frac)
    return make_compressor(spec.compressor, **kwargs)


_PLANE_DTYPES = {"f32": jnp.float32, "float32": jnp.float32,
                 "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}


def resolve_plane_dtype(spec_or_name) -> Optional[Any]:
    """``spec.plane_dtype`` -> a concrete jnp dtype or None (legacy f32).

    Accepts an :class:`ExperimentSpec`, a name ('f32'/'bf16' and their long
    spellings), or a dtype-like; validates against the engine's supported
    planes (f32 exact, bf16 with stochastic-rounding writeback).
    """
    val = (spec_or_name.plane_dtype
           if isinstance(spec_or_name, ExperimentSpec) else spec_or_name)
    if val is None:
        return None
    if isinstance(val, str):
        if val not in _PLANE_DTYPES:
            raise ValueError(f"unknown plane_dtype {val!r}; have "
                             f"{sorted(_PLANE_DTYPES)}")
        val = _PLANE_DTYPES[val]
    dt = jnp.dtype(val)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"plane_dtype must be f32 or bf16, got {dt}")
    return dt


def _apply_remat(loss_fn, policy: Optional[str]):
    """Wrap ``loss_fn`` in jax.checkpoint per ``spec.remat_policy``.

    The registered algorithms differentiate the loss inside their step
    (``jax.value_and_grad`` in ``_agent_gradient``), so checkpointing the
    loss function itself is exactly "remat around the loss/grad": the
    backward pass recomputes activations instead of keeping the whole
    forward resident -- what makes the models/ stack fit next to eight
    agent-stacked state buffers.
    """
    if policy is None:
        return loss_fn
    if policy == "full":
        return jax.checkpoint(loss_fn)
    if policy == "dots":
        return jax.checkpoint(
            loss_fn, policy=jax.checkpoint_policies.dots_saveable)
    raise ValueError(f"unknown remat_policy {policy!r}; have None, "
                     "'full', 'dots'")


def resolve_wire_format(spec: ExperimentSpec):
    """``spec.wire`` -> a :class:`repro.core.wire_formats.WireFormat` or None.

    'packed_bits' registers the compressor family's bit-packed layout
    (top_k / block_top_k -> bf16+u16 ``topk_bits``; qsgd -> uint32
    ``qsgd_bits`` with the spec's ``levels``) and routes pack/unpack through
    the fused Pallas kernels whenever the comm backend resolves to pallas.
    """
    if spec.wire == "dense":
        return None
    if spec.wire != "packed_bits":
        raise ValueError(f"unknown wire format {spec.wire!r}; have "
                         f"{wire_formats.WIRE_MODES}")
    if spec.gossip_mode not in ("ring", "packed"):
        raise ValueError(
            "wire='packed_bits' needs gossip_mode 'ring' or 'packed' "
            f"(got {spec.gossip_mode!r}); dense gossip ships the dense "
            "emulation by definition")
    use_pallas = resolve_backend(spec.comm_backend) == "pallas"
    if spec.compressor == "qsgd":
        levels = int(spec.compressor_kwargs.get("levels", 16))
        return wire_formats.make_wire_format(
            "qsgd", levels=levels, use_pallas=use_pallas,
            interpret=spec.interpret)
    return wire_formats.make_wire_format(
        spec.compressor, frac=spec.frac, use_pallas=use_pallas,
        interpret=spec.interpret)


def resolve_gamma(spec: ExperimentSpec, topology: Topology,
                  compressor: Compressor,
                  schedule: Optional[TopologySchedule] = None) -> float:
    """The paper's consensus stepsize: gamma_scale * (1 - alpha) * rho.

    Under a time-varying schedule ``alpha`` is the schedule's per-round
    geometric mixing rate (joint_alpha^(1/period)) -- an individual churn
    round may not mix at all, but the window does, and that is the rate
    consensus actually contracts by.  A period-1 schedule reproduces the
    static derivation exactly."""
    if spec.gamma is not None:
        return spec.gamma
    alpha = topology.alpha if schedule is None else schedule.alpha
    gamma = spec.gamma_scale * (1.0 - alpha) * compressor.rho
    if gamma <= 0.0:
        # e.g. low_rank advertises rho=0 (data-dependent contraction):
        # a zero gamma would silently disable gossip and train agents in
        # isolation, so demand an explicit choice instead
        raise ValueError(
            f"derived gamma is 0 (alpha={alpha:.4g}, "
            f"rho={compressor.rho:.4g} for {compressor.name}); pass an "
            "explicit gamma= in the ExperimentSpec")
    return gamma


def _check_fleet_spec(spec: ExperimentSpec, algo: Optional[str] = None):
    """Reject spec combinations the fleet executor cannot honour."""
    if spec.gossip_mode != "dense":
        raise ValueError(
            f"fleet mode applies mixing as one vectorized dense/COO sweep "
            f"over the whole fleet axis; gossip_mode={spec.gossip_mode!r} "
            "is a per-device wire executor -- use gossip_mode='dense'")
    if spec.wire != "dense":
        raise ValueError(
            f"fleet mode ships no per-link packed buffers (the simulated "
            f"fleet axis is device-local); wire={spec.wire!r} -- use "
            "wire='dense'")
    if algo in _PUSH_SUM_ALGOS and spec.n_agents > FLEET_DENSE_GATE:
        raise ValueError(
            f"{algo} initializes its push-sum mirrors from the dense "
            f"round-0 mixing table; fleet mode supports it only at "
            f"n_agents <= {FLEET_DENSE_GATE} (got {spec.n_agents})")


def resolve_fleet_topology(spec: ExperimentSpec):
    """Fleet topology: the ordinary dense resolution at
    n <= FLEET_DENSE_GATE (per-device bit parity), the sparse COO builders
    of :mod:`repro.core.fleet` above it (make_topology's Python O(n^2)
    weight loops and dense eigensolves do not survive n = 100k)."""
    if spec.n_agents <= FLEET_DENSE_GATE:
        return resolve_topology(spec)
    return fleet_topology(spec.topology, spec.n_agents,
                          weights=spec.topology_weights, p=spec.topology_p,
                          seed=spec.topology_seed)


def resolve_fleet_schedule(spec: ExperimentSpec, topology=None):
    """Fleet analogue of :func:`resolve_schedule`: dense resolution below
    the gate, sparse generators ('rotate:...', 'erdos_renyi:...') above.
    Directed (column-stochastic) schedules never take the fleet path."""
    if spec.topology_schedule is None:
        return None
    if spec.n_agents <= FLEET_DENSE_GATE:
        top = topology if isinstance(topology, Topology) else None
        sched = resolve_schedule(spec, top)
        if sched is not None and sched.is_directed:
            raise ValueError(
                "fleet mode mixes with doubly-stochastic tables only; "
                f"{spec.topology_schedule!r} is column-stochastic (push-sum "
                "runs per-device, fleet=False)")
        return sched
    text = spec.topology_schedule
    kind, _, rest = text.partition(":")
    kind = kind.strip()
    if kind == "rotate":
        first, _, more = rest.partition(",")
        if "=" not in first:
            kv = {"kinds": first.strip(), **_parse_schedule_kv(more)}
        else:
            kv = dict(_parse_schedule_kv(rest))
        kinds = [k for k in kv.pop("kinds", "").split("+") if k]
        if not kinds:
            raise ValueError("rotate schedule needs '+'-separated graph "
                             "kinds, e.g. 'rotate:ring+exponential'")
        sched = fleet_rotating_schedule(
            kinds, spec.n_agents,
            weights=kv.pop("weights", spec.topology_weights),
            seed=int(kv.pop("seed", spec.topology_seed)))
    elif kind == "erdos_renyi":
        kv = dict(_parse_schedule_kv(rest))
        degree = kv.pop("degree", None)
        sched = fleet_er_schedule(
            spec.n_agents, period=int(kv.pop("period", 4)),
            degree=None if degree is None else int(degree),
            weights=kv.pop("weights", spec.topology_weights),
            seed=int(kv.pop("seed", spec.topology_seed)))
    else:
        raise ValueError(
            f"fleet mode at n_agents={spec.n_agents} > {FLEET_DENSE_GATE} "
            f"supports the sparse generators 'rotate:...' and "
            f"'erdos_renyi:...'; got {text!r}")
    if kv:
        raise ValueError(f"unknown fleet {kind!r} schedule keys "
                         f"{sorted(kv)} in {text!r}")
    return sched


def build_engine(spec: ExperimentSpec, *,
                 mesh=None, agent_axes: Sequence[str] = ("data",),
                 leaf_specs=None, compress_fn=None,
                 topology: Optional[Topology] = None,
                 schedule: Optional[TopologySchedule] = None) -> CommRound:
    """Comm-round engine for ``spec`` (compressor + mixer + backend).

    The only sanctioned way to get a :class:`CommRound` outside repro.core;
    benchmarks that exercise the engine directly use this instead of wiring
    make_topology/make_mixer/CommRound by hand.

    mesh/leaf_specs/agent_axes feed both the gossip executor (ring/packed
    wire formats) and the engine's pallas path: on a mesh the fused update
    runs on per-shard planes inside shard_map, so ``comm_backend='pallas'``
    stays reshard-free on every layout.

    When the spec declares a ``topology_schedule`` (or ``schedule`` is
    passed directly), the mixer is built from the schedule's stacked table
    and the engine's round methods must be fed the absolute round index
    (every registered algorithm passes its state's step counter).
    """
    if spec.fleet:
        _check_fleet_spec(spec)
        top = resolve_fleet_topology(spec) if topology is None else topology
        sched = (resolve_fleet_schedule(spec, top) if schedule is None
                 else schedule)
    else:
        top = resolve_topology(spec) if topology is None else topology
        sched = resolve_schedule(spec, top) if schedule is None else schedule
    comp = resolve_compressor(spec)
    codec = resolve_wire_format(spec)
    if codec is not None and compress_fn is not None:
        raise ValueError(
            "wire='packed_bits' fuses (shard-local) compression with "
            "packing inside the codec executor; a compress_fn override "
            "would be silently ignored -- drop it (launch.steps skips the "
            "shard-local compressor automatically under packed_bits)")
    if spec.fleet:
        mixer = make_fleet_mixer(sched if sched is not None else top)
    else:
        mixer = make_mixer(sched if sched is not None else top,
                           spec.gossip_mode, mesh=mesh, frac=spec.frac,
                           agent_axes=agent_axes, leaf_specs=leaf_specs,
                           codec=codec)
    return CommRound(compressor=comp, mixer=mixer, compress_fn=compress_fn,
                     backend=spec.comm_backend, interpret=spec.interpret,
                     mesh=mesh, leaf_specs=leaf_specs,
                     agent_axes=tuple(agent_axes), overlap=spec.overlap,
                     plane_dtype=resolve_plane_dtype(spec))


def build(spec: ExperimentSpec, loss_fn, *,
          mesh=None, agent_axes: Sequence[str] = ("data",), leaf_specs=None,
          compress_fn=None, topology: Optional[Topology] = None) -> Algorithm:
    """Resolve ``spec`` into a ready-to-train :class:`Algorithm`.

    loss_fn: (params, batch) -> scalar loss, per agent.
    mesh / agent_axes / leaf_specs: sharded-launch hooks, forwarded to the
      gossip executor (required for 'ring'/'packed' wire formats).
    compress_fn: optional (key, tree) -> tree compression override (e.g.
      the shard-local compressor from repro.launch.steps).
    topology: pre-built Topology override; by default the spec's
      topology fields are resolved via make_topology.
    """
    info = algorithm_info(spec.algo)
    loss_fn = _apply_remat(loss_fn, spec.remat_policy)
    top, sched = None, None
    if info.decentralized:
        if spec.fleet:
            _check_fleet_spec(spec, algo=spec.algo)
            top = (resolve_fleet_topology(spec) if topology is None
                   else topology)
            sched = resolve_fleet_schedule(spec, top)
        else:
            top = resolve_topology(spec) if topology is None else topology
            sched = resolve_schedule(spec, top)
        if sched is not None and sched.is_directed \
                and spec.algo not in _PUSH_SUM_ALGOS:
            raise ValueError(
                f"{spec.algo} assumes doubly-stochastic mixing but "
                f"{spec.topology_schedule!r} is column-stochastic "
                "(directed): without push-sum de-biasing the iterates "
                "drift toward the Perron vector -- use algo='dp-csgp' "
                "for directed topologies")
    comp, mixer, engine = None, None, None
    if info.decentralized and info.compressed:
        # the one engine-construction path, shared with microbenchmarks
        engine = build_engine(spec, mesh=mesh, agent_axes=agent_axes,
                              leaf_specs=leaf_specs,
                              compress_fn=compress_fn, topology=top,
                              schedule=sched)
        comp, mixer = engine.compressor, engine.mixer
    elif info.decentralized:
        if spec.fleet:
            mixer = make_fleet_mixer(sched if sched is not None else top)
        else:
            mixer = make_mixer(sched if sched is not None else top,
                               spec.gossip_mode, mesh=mesh, frac=spec.frac,
                               agent_axes=agent_axes, leaf_specs=leaf_specs)
    elif info.compressed:
        # server/client: compression without gossip
        comp = resolve_compressor(spec)
        engine = CommRound(compressor=comp, mixer=None,
                           compress_fn=compress_fn,
                           backend=spec.comm_backend,
                           interpret=spec.interpret,
                           mesh=mesh, leaf_specs=leaf_specs,
                           agent_axes=tuple(agent_axes),
                           plane_dtype=resolve_plane_dtype(spec))
    gamma = None
    if info.decentralized:
        gamma = (resolve_gamma(spec, top, comp, sched) if info.compressed
                 else (1.0 if spec.gamma is None else spec.gamma))
    r = Resolved(info=info, topology=top, compressor=comp, mixer=mixer,
                 engine=engine, gamma=gamma, schedule=sched)
    return get_factory(spec.algo)(spec, loss_fn, r)


def _bind_init(spec: ExperimentSpec, r: Resolved, init_fn):
    """Uniform init(params, n_agents=None, w=None) with spec defaults.

    ``w`` is passed through as given: every init here broadcasts a single
    replica, so W X^0 = X^0 exactly (rows of W sum to 1) and the default
    no-mix path is both correct and free -- materializing topology.w at
    init would cost an O(n^2 d) einsum on the large-model launch path for
    a bit-identical result.
    """

    def init(params, n_agents: Optional[int] = None, w=None):
        n = spec.n_agents if n_agents is None else n_agents
        return init_fn(params, n, w)

    return init


def _algorithm(spec, r, *, state_cls, init, step, config=None) -> Algorithm:
    return Algorithm(name=spec.algo, info=r.info, spec=spec,
                     state_cls=state_cls, init=init, step=step,
                     topology=r.topology, compressor=r.compressor,
                     mixer=r.mixer, engine=r.engine, gamma=r.gamma,
                     config=config, schedule=r.schedule)


# ---------------------------------------------------------------------------
# the eleven registered entry points
# ---------------------------------------------------------------------------

# algorithms that de-bias column-stochastic (directed) mixing correctly;
# everything else is rejected by build() when handed a directed schedule
_PUSH_SUM_ALGOS = frozenset({"dp-csgp"})


def _require_tau(spec: ExperimentSpec) -> float:
    """DP oracles calibrate noise to tau's sensitivity -- no clipping, no
    guarantee -- so tau=None is an error rather than a silent fallback."""
    if spec.tau is None:
        raise ValueError(f"{spec.algo} is a DP algorithm: its Gaussian "
                         "noise is calibrated to the clipping threshold, "
                         "so tau=None (unclipped) would void the privacy "
                         "guarantee -- set a finite tau")
    return spec.tau


def _porter_family(spec: ExperimentSpec, loss_fn, r: Resolved, variant: str,
                   adam: bool = False) -> Algorithm:
    if variant == "gc" and spec.tau is None:
        # unclipped PORTER-GC *is* BEER (paper Section 4.3); routing through
        # beer_config keeps the no-clip point exact instead of feeding
        # tau=inf into the smooth clip factor (inf/(inf+nrm) is NaN)
        variant = "beer"
    # under bf16 planes the stored gradient g_prev is a bf16 buffer, so the
    # fresh gradient must be cast to the same dtype -- otherwise the state's
    # dtype flips between init and step and scan/chunked carries diverge
    pdt = resolve_plane_dtype(spec)
    grad_dtype = spec.buffer_dtype if pdt is None else pdt
    if variant == "beer":
        cfg = beer_config(spec.eta, r.gamma, clip_mode=spec.clip_mode,
                          grad_dtype=grad_dtype)
    else:
        tau = (_require_tau(spec) if variant == "dp"
               else (float("inf") if spec.tau is None else spec.tau))
        cfg = PorterConfig(eta=spec.eta, gamma=r.gamma, tau=tau,
                           variant=variant, clip_mode=spec.clip_mode,
                           sigma_p=spec.sigma_p,
                           grad_dtype=grad_dtype)
    if adam:
        step = functools.partial(porter_adam_step, cfg, loss_fn, None, None,
                                 engine=r.engine, b1=spec.b1, b2=spec.b2,
                                 adam_eps=spec.adam_eps)
        init = _bind_init(
            spec, r, functools.partial(porter_adam_init, plane_dtype=pdt))
        return _algorithm(spec, r, state_cls=PorterAdamState, init=init,
                          step=step, config=cfg)
    step = functools.partial(porter_step, cfg, loss_fn, None, None,
                             engine=r.engine)
    init = _bind_init(
        spec, r,
        functools.partial(porter_init, buffer_dtype=spec.buffer_dtype,
                          plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=PorterState, init=init, step=step,
                      config=cfg)


@register_algorithm("porter-gc", comm_rounds=2)
def _build_porter_gc(spec, loss_fn, r):
    return _porter_family(spec, loss_fn, r, "gc")


@register_algorithm("porter-dp", dp=True, comm_rounds=2)
def _build_porter_dp(spec, loss_fn, r):
    return _porter_family(spec, loss_fn, r, "dp")


@register_algorithm("beer", comm_rounds=2)
def _build_beer(spec, loss_fn, r):
    return _porter_family(spec, loss_fn, r, "beer")


@register_algorithm("porter-adam", comm_rounds=2)
def _build_porter_adam(spec, loss_fn, r):
    return _porter_family(spec, loss_fn, r, "gc", adam=True)


@register_algorithm("dsgd", compressed=False, comm_rounds=1)
def _build_dsgd(spec, loss_fn, r):
    step = functools.partial(BL.dsgd_step, spec.eta, r.gamma, loss_fn,
                             r.mixer, tau=spec.tau, clip_mode=spec.clip_mode,
                             sigma_p=spec.sigma_p, dp=spec.dp)
    init = _bind_init(spec, r, lambda params, n, w: BL.dsgd_init(params, n))
    return _algorithm(spec, r, state_cls=BL.DsgdState, init=init, step=step)


@register_algorithm("choco", comm_rounds=1)
def _build_choco(spec, loss_fn, r):
    step = functools.partial(BL.choco_step, spec.eta, r.gamma, loss_fn,
                             None, None, engine=r.engine, tau=spec.tau,
                             clip_mode=spec.clip_mode)
    pdt = resolve_plane_dtype(spec)
    init = _bind_init(
        spec, r,
        lambda params, n, w: BL.choco_init(params, n, plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=BL.ChocoState, init=init, step=step)


@register_algorithm("dp-sgd", dp=True, decentralized=False, compressed=False)
def _build_dpsgd(spec, loss_fn, r):
    tau = _require_tau(spec)

    def step(state, batch, key):
        # the registry protocol feeds agent-stacked batches (n_agents, b,
        # ...); the central server pools them into one batch of n*b
        # samples.  Validate the contract instead of guessing from ndim.
        lead = {l.shape[0] for l in jax.tree_util.tree_leaves(batch)
                if hasattr(l, "shape") and l.ndim >= 1}
        if lead != {spec.n_agents}:
            raise ValueError(
                f"dp-sgd consumes agent-stacked batches with leading dim "
                f"n_agents={spec.n_agents} (the registry's uniform batch "
                f"layout); got leading dims {sorted(lead)} -- call "
                "repro.core.baselines.dpsgd_step directly for plain "
                "central batches")
        flat = jax.tree_util.tree_map(
            lambda l: l.reshape((-1,) + l.shape[2:]) if l.ndim >= 2 else l,
            batch)
        return BL.dpsgd_step(spec.eta, loss_fn, state, flat, key, tau=tau,
                             clip_mode=spec.clip_mode, sigma_p=spec.sigma_p)

    def init(params, n_agents=None, w=None):
        del n_agents, w  # single server replica
        return BL.dpsgd_init(params)

    return _algorithm(spec, r, state_cls=BL.DpSgdState, init=init, step=step)


@register_algorithm("dp-csgp", dp=True, comm_rounds=2)
def _build_dp_csgp(spec, loss_fn, r):
    tau = _require_tau(spec)
    pdt = resolve_plane_dtype(spec)
    cfg = PorterConfig(eta=spec.eta, gamma=r.gamma, tau=tau, variant="dp",
                       clip_mode=spec.clip_mode, sigma_p=spec.sigma_p,
                       grad_dtype=spec.buffer_dtype if pdt is None else pdt)
    step = functools.partial(dp_csgp_step, cfg, loss_fn, None, None,
                             engine=r.engine)
    # the push-sum mirrors need the actual round-0 matrix (m = W q with a
    # column-stochastic W has no no-mix shortcut -- see dp_csgp_init)
    w0 = r.schedule.ws[0] if r.schedule is not None else r.topology.w
    init = _bind_init(
        spec, r,
        functools.partial(dp_csgp_init, w0=w0,
                          buffer_dtype=spec.buffer_dtype, plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=DpCsgpState, init=init, step=step,
                      config=cfg)


@register_algorithm("clip21", comm_rounds=2)
def _build_clip21(spec, loss_fn, r):
    # clip21 clips the *residual*, always piecewise: the smooth factor
    # tau/(tau+||delta||) never reaches 1, so the EF estimate could never
    # lock onto the gradient (and tau=inf would be NaN) -- see core/clip21
    pdt = resolve_plane_dtype(spec)
    tau = float("inf") if spec.tau is None else spec.tau
    cfg = PorterConfig(eta=spec.eta, gamma=r.gamma, tau=tau, variant="gc",
                       clip_mode="piecewise",
                       grad_dtype=spec.buffer_dtype if pdt is None else pdt)
    step = functools.partial(clip21_step, cfg, loss_fn, None, None,
                             engine=r.engine)
    init = _bind_init(
        spec, r,
        functools.partial(clip21_init, buffer_dtype=spec.buffer_dtype,
                          plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=Clip21State, init=init, step=step,
                      config=cfg)


@register_algorithm("subgrad-comp", comm_rounds=1)
def _build_subgrad(spec, loss_fn, r):
    step = functools.partial(subgrad_step, spec.eta, r.gamma, loss_fn,
                             None, None, engine=r.engine, tau=spec.tau,
                             clip_mode=spec.clip_mode)
    pdt = resolve_plane_dtype(spec)
    init = _bind_init(
        spec, r,
        lambda params, n, w: subgrad_init(params, n, plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=SubgradState, init=init, step=step)


@register_algorithm("soteriafl", dp=True, decentralized=False)
def _build_soteriafl(spec, loss_fn, r):
    tau = _require_tau(spec)
    step = functools.partial(BL.soteria_step, spec.eta, spec.alpha_shift,
                             loss_fn, None, engine=r.engine, tau=tau,
                             clip_mode=spec.clip_mode, sigma_p=spec.sigma_p)
    pdt = resolve_plane_dtype(spec)
    init = _bind_init(
        spec, r,
        lambda params, n, w: BL.soteria_init(params, n, plane_dtype=pdt))
    return _algorithm(spec, r, state_cls=BL.SoteriaState, init=init,
                      step=step)
