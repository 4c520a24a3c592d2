"""Reduction of a profiler trace to device events, and the arithmetic the
per-layer readers share.

``reduce(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps, for each TPU device plane, the events of its ``XLA Ops`` line:
name, start, end, the HLO category, and the op's scope path (the
``jax.named_scope`` names of the ops that the benchmark wraps), plus the
host's annotated spans.  Everything after that is plain interval
arithmetic on those events, kept here so that a CPU test can check it on a
small recorded trace.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
# the benchmark's host spans (TraceAnnotation names in run.py)
HOST_SPANS = ("chipbench.dispatch", "chipbench.wait")
# the named scopes the benchmark puts around what it hands the program
SCOPES = ("grad_oracle", "compress")
# the gossip executors' collectives: the ring's permutes, dense gossip's
# all-gathers (an all-reduce of the step's metrics is not gossip)
GOSSIP = ("collective-permute", "all-gather")
# ops whose events enclose other ops' events on the same line
CONTAINERS = ("while", "conditional", "call")
KERNEL = "custom-call/tpu_custom_call"

_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_META = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*"
                   r'op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # the HLO instruction's name
    start: int          # ns
    end: int            # ns
    category: str = ""  # its opcode; custom calls as custom-call/<target>
    scope: str = ""     # its op_name: jit, named scopes and primitive

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]             # plane name -> op events
    host: List[Event]                           # the benchmark's host spans

    def window(self) -> Tuple[int, int]:
        """First and last instant of any device op or host span."""
        evs = [e for es in self.devices.values() for e in es] + self.host
        return min(e.start for e in evs), max(e.end for e in evs)


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def parse_op(text: str, scopes: Dict[str, str]) -> Tuple[str, str, str]:
    """(name, category, scope) of a device event named by its HLO text."""
    m = _INSTR.match(text)
    if not m:
        return text, "", ""
    name = m.group(1)
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    category = op.group(1) if op else ""
    if category == "custom-call":
        t = _TARGET.search(rest)
        category += "/" + (t.group(1) if t else "")
    return name, category, scopes.get(name, "")


def reduce(source, scopes: Optional[Dict[str, str]] = None) -> Trace:
    """Device op events (containers left out) and the benchmark's host
    spans of a trace file (or a loaded ``ProfileData``); ``scopes`` maps
    instruction names to op_names."""
    from jax.profiler import ProfileData
    pd = (source if isinstance(source, ProfileData)
          else ProfileData.from_file(str(source)))
    scopes = scopes or {}
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = []
                for e in line.events:
                    name, cat, scope = parse_op(e.name, scopes)
                    if cat in CONTAINERS:
                        continue
                    evs.append(Event(name, int(e.start_ns), int(e.end_ns),
                                     cat, scope))
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.end_ns)))
    return Trace(devices=devices, host=sorted(host, key=lambda e: e.start))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> List[Tuple[int, int]]:
    """Merged, sorted union of (start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    """Time in [lo, hi) during which any op runs."""
    return covered(clip([(e.start, e.end) for e in events], lo, hi))


def matching_ns(events: List[Event], lo: int, hi: int, pred) -> int:
    """Union time of the ops that ``pred`` selects (nesting counted once)."""
    return covered(clip([(e.start, e.end) for e in events if pred(e)],
                        lo, hi))


def in_scope(scope: str):
    """Selects ops whose scope path holds the named scope ``scope``."""
    def pred(e: Event) -> bool:
        parts = e.scope.replace("(", "/").replace(")", "/").split("/")
        return scope in parts
    return pred


def is_kernel(e: Event) -> bool:
    """A Mosaic (Pallas) kernel: a TPU custom call."""
    return e.category == KERNEL


def is_gossip(e: Event) -> bool:
    return e.category.startswith(GOSSIP)


def exposed_ns(events: List[Event], lo: int, hi: int) -> int:
    """Gossip collective time during which no other op runs on the
    device."""
    coll = union(clip([(e.start, e.end) for e in events if is_gossip(e)],
                      lo, hi))
    comp = union(clip([(e.start, e.end) for e in events
                       if not is_gossip(e)], lo, hi))
    exposed, j = 0, 0
    for s, e in coll:
        covered_here = 0
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered_here += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
        exposed += (e - s) - covered_here
    return exposed


def top_ops(events: List[Event], lo: int, hi: int, n: int = 10):
    """[(op family, seconds)] of the ops that took most device time; a
    family is the opcode (a kernel: its name) and the innermost named
    scope of the benchmark's it ran under."""
    tot = defaultdict(int)
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            tot[family(e)] += t - s
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def family(e: Event) -> str:
    """'ef_track.166' -> 'ef_track'; 'fusion.12' under the gradient scope
    -> 'fusion@grad_oracle'."""
    if e.category == KERNEL:
        head, _, tail = e.name.rpartition(".")
        return head if head and tail.isdigit() else e.name
    for scope in SCOPES:
        if in_scope(scope)(e):
            return f"{e.category}@{scope}"
    return e.category or e.name


def idle_gaps(events: List[Event], host: List[Event], lo: int, hi: int,
              n: int = 10):
    """[(what the host was doing, seconds)] of the longest device gaps."""
    busy = union(clip([(e.start, e.end) for e in events], lo, hi))
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        doing = next((h.name for h in host if h.start <= mid < h.end),
                     "host outside the benchmark's spans")
        out.append([doing, (e - s) / 1e9])
    return out


def trace_file(log_dir) -> Optional[Path]:
    files = sorted(Path(log_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None
