"""Device time per round of the gradient oracle: the ops under the named
scope the benchmark puts around the loss it hands to the build (forward
and transpose), averaged over the cell's chips, in ms."""

from chipbench import trace as T
from chipbench.cell import GRAD_SCOPE


def read(ctx):
    t = [T.matching_ns(evs, ctx["lo"], ctx["hi"], T.in_scope(GRAD_SCOPE))
         for evs in ctx["devices"]]
    if not any(t) or not ctx["rounds"]:
        return None
    return sum(t) / len(t) / ctx["rounds"] / 1e6
