"""Share of the traced window in which no operation runs on the device,
averaged over the cell's chips: 100 (1 - busy / window)."""

from chipbench import trace as T


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    if hi <= lo or not ctx["devices"]:
        return None
    busy = [T.busy_ns(evs, lo, hi) for evs in ctx["devices"]]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
