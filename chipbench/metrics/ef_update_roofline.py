"""The EF-update kernels' share of their HBM roofline: the least bytes one
round's EF updates must move on a chip (counts.engine_round_bytes over the
cell's chips) at the device's HBM rate, over the device time per round of
the kernels that make those updates (``ef_track``, ``ef_step`` and the
stochastic-rounding writeback ``sr_cast``), in %.  The wire codec's kernels
are not counted: their bytes are not in the count."""

from chipbench import trace as T

EF_KERNELS = ("ef_track", "ef_step", "sr_cast")


def is_ef_kernel(e) -> bool:
    return T.is_kernel(e) and T.family(e) in EF_KERNELS


def read(ctx):
    t = [T.matching_ns(evs, ctx["lo"], ctx["hi"], is_ef_kernel)
         for evs in ctx["devices"]]
    if not any(t) or not ctx["rounds"]:
        return None
    kernel_s = sum(t) / len(t) / ctx["rounds"] / 1e9
    least_s = (ctx["round_bytes"] / ctx["chips"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
