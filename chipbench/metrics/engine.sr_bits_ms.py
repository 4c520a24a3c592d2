"""Device time per round of the stochastic-rounding bits: the ops under
the program's ``engine.sr_bits`` scope (the u32 draw for every bf16
writeback and its keys; a part of ``engine.ef_update_ms``), averaged over
the cell's chips, in ms."""

from chipbench import scopes as S


def read(ctx):
    return S.scope_ms(ctx, "engine.sr_bits")
