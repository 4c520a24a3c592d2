"""Device time per round of gradient clipping: the ops under the program's
``oracle.clip`` scope (GC's clip of the batch gradient; DP's per-sample
norm, scale and sum), averaged over the cell's chips, in ms."""

from chipbench import scopes as S


def read(ctx):
    return S.scope_ms(ctx, "oracle.clip")
