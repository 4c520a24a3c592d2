"""The program's own layer names, and the arithmetic of the per-layer
readers that read them.

The program (``src/repro``) names each layer of a training round with a
``jax.named_scope``, which the compiled program keeps in every HLO
instruction's op_name; the reduction (``trace.py``) gives each device event
that op_name as its ``scope``.  A fusion carries its root instruction's
op_name, so work that XLA fuses across a boundary counts for the scope of
the consumer.  The chunk runner also writes host spans
(``jax.profiler.TraceAnnotation``) on the profiler's clock; ``trace.reduce``
keeps only the benchmark's own host spans, so the runner's are read here,
from the same trace file.

A program without these names (an older commit) gives no events and no
spans: every reader here then returns None.
"""

from __future__ import annotations

from pathlib import Path
from typing import List, Optional

from chipbench import trace as T

HERE = Path(__file__).resolve().parent
# where run.py writes a traced run's profile (one directory per cell)
TRACE_DIR = HERE / ".trace"

# every named scope of the program, each one path element of an op_name
PROGRAM_SCOPES = (
    "oracle",            # the local gradient, its clip and noise included
    "oracle.clip",       # the batch clip (GC) or per-sample clip-and-sum
    "oracle.noise",      # the DP Gaussian draw and add
    "engine.compress",   # the increment y - q and its compression / pack
    "engine.mix",        # gossip: the mix W c and the wire between agents
    "engine.ef_update",  # the EF updates: kernels, SR writeback, glue
    "engine.sr_bits",    # the stochastic-rounding bits (in ef_update)
    "step.metrics",      # consensus errors, norms, wire bytes
    "runner.batch",      # in-program batch synthesis
)
# the chunk runner's host spans
PROGRAM_SPANS = ("runner.dispatch", "runner.on_chunk")


def in_any_program_scope(e: T.Event) -> bool:
    parts = e.scope.replace("(", "/").replace(")", "/").split("/")
    return any(s in parts for s in PROGRAM_SCOPES)


def ms_per_round(ctx, pred) -> Optional[float]:
    """Device time per round of the ops ``pred`` selects (nesting counted
    once), averaged over the cell's chips, in ms; None where it selects
    nothing."""
    t = [T.matching_ns(evs, ctx["lo"], ctx["hi"], pred)
         for evs in ctx["devices"]]
    if not any(t) or not ctx["rounds"]:
        return None
    return sum(t) / len(t) / ctx["rounds"] / 1e6


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device time per round under the program scope ``scope``."""
    return ms_per_round(ctx, T.in_scope(scope))


def unscoped_ms(ctx) -> Optional[float]:
    """Device time per round under none of the program's scopes, averaged
    over the cell's chips, in ms; None where no op has one."""
    lo, hi = ctx["lo"], ctx["hi"]
    devices = ctx["devices"]
    scoped = [T.matching_ns(evs, lo, hi, in_any_program_scope)
              for evs in devices]
    if not any(scoped) or not ctx["rounds"]:
        return None
    rest = [T.busy_ns(evs, lo, hi) - s for evs, s in zip(devices, scoped)]
    return sum(rest) / len(rest) / ctx["rounds"] / 1e6


def program_spans(source, names=PROGRAM_SPANS) -> List[T.Event]:
    """The program's host spans named ``names`` in a trace file (or a
    loaded ``ProfileData``), by start."""
    from jax.profiler import ProfileData
    pd = (source if isinstance(source, ProfileData)
          else ProfileData.from_file(str(source)))
    out = [T.Event(e.name, int(e.start_ns), int(e.end_ns))
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events if e.name in names]
    return sorted(out, key=lambda e: e.start)


def latest_trace() -> Optional[Path]:
    """The trace file of the run that was traced last in this checkout."""
    return T.trace_file(TRACE_DIR) if TRACE_DIR.is_dir() else None


def mean_span_ms(spans: List[T.Event], name: str, lo: int,
                 hi: int) -> Optional[float]:
    """Mean duration in ms of the spans named ``name`` that start in
    [lo, hi); None where there are none."""
    durs = [e.dur for e in spans if e.name == name and lo <= e.start < hi]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
