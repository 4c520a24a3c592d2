"""Device time per round of the Mosaic kernels (TPU custom calls), which on
the train path are the comm-round engine's, averaged over the cell's
chips, in ms."""

from chipbench import trace as T


def read(ctx):
    t = [T.matching_ns(evs, ctx["lo"], ctx["hi"], T.is_kernel)
         for evs in ctx["devices"]]
    if not any(t) or not ctx["rounds"]:
        return None
    return sum(t) / len(t) / ctx["rounds"] / 1e6
