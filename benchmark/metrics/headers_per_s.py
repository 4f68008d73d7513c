"""Headers a light client certifies per second: the headers of the whole
passes of lite.certify_chain over the sum of those passes' own times;
nothing counted at a cut-off, the decoding between passes not in it. A
metric of its own and not `commits_per_s`, so that the lite cell, whose
runs spread by 1.1%, is not held to the bound fast-sync's 2.9% needs."""

from benchmark.passes import rate

LAYER = "end to end"
MOVES = None


def read(r):
    return rate(r.passes)
