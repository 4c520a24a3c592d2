"""Device time per round of the DP perturbation: the ops under the
program's ``oracle.noise`` scope (the Gaussian draw and its add), averaged
over the cell's chips, in ms."""

from chipbench import scopes as S


def read(ctx):
    return S.scope_ms(ctx, "oracle.noise")
