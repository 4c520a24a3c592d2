"""Device time per round of compression: the ops under the named scope of
the benchmark's ``compress_fn`` (the engine's own per-agent compression),
averaged over the cell's chips, in ms."""

from chipbench import trace as T
from chipbench.cell import COMPRESS_SCOPE


def read(ctx):
    t = [T.matching_ns(evs, ctx["lo"], ctx["hi"], T.in_scope(COMPRESS_SCOPE))
         for evs in ctx["devices"]]
    if not any(t) or not ctx["rounds"]:
        return None
    return sum(t) / len(t) / ctx["rounds"] / 1e6
