"""Device time per round of gossip: the ops under the program's
``engine.mix`` scope (the mix ``W c`` on one chip; permutes, all-gathers
and band arithmetic on a mesh), less those of the packing that a codec
executor runs inside its mix (``engine.compress``), averaged over the
cell's chips, in ms."""

from chipbench import scopes as S
from chipbench import trace as T


def is_mix(e) -> bool:
    return T.in_scope("engine.mix")(e) and not T.in_scope(
        "engine.compress")(e)


def read(ctx):
    return S.ms_per_round(ctx, is_mix)
