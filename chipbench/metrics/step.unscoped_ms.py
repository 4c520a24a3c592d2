"""Device time per round that no layer of the program owns: busy time
under none of the program's named scopes (``scopes.PROGRAM_SCOPES``),
averaged over the cell's chips, in ms.  A program without the scopes gives
no reading."""

from chipbench import scopes as S


def read(ctx):
    return S.unscoped_ms(ctx)
