"""Gossip collective time per round (the ring's collective-permutes, dense
gossip's all-gathers) during which no other op runs on the device, the
largest over the cell's chips, in ms; 0 is a reading, a trace with no
gossip collective is none."""

from chipbench import trace as T


def read(ctx):
    devices, lo, hi = ctx["devices"], ctx["lo"], ctx["hi"]
    if not ctx["rounds"] or not any(
            T.matching_ns(evs, lo, hi, T.is_gossip) for evs in devices):
        return None
    worst = max(T.exposed_ns(evs, lo, hi) for evs in devices)
    return worst / ctx["rounds"] / 1e6
