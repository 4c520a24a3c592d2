"""Device time per round of the gradient oracle as the program names it:
the ops under its ``oracle`` scope (forward, backward, the clip and, under
DP, the noise), averaged over the cell's chips, in ms."""

from chipbench import scopes as S


def read(ctx):
    return S.scope_ms(ctx, "oracle")
