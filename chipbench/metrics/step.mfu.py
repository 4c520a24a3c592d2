"""Model FLOP utilization of the whole step in the traced window: model
FLOPs per token (counts.flops_per_token) x trained tokens/s / (chips x the
device's bf16 peak), in %."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
