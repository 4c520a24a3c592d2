"""Device time per round of the comm-round engine's EF updates: the ops
under the program's ``engine.ef_update`` scope (the EF kernels, the
stochastic-rounding writeback with its bits, and the f32 glue around
them), averaged over the cell's chips, in ms."""

from chipbench import scopes as S


def read(ctx):
    return S.scope_ms(ctx, "engine.ef_update")
