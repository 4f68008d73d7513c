"""Blocks a joining node verifies AND applies per second: the blocks of
the whole fast-sync passes over the sum of those passes' own times;
nothing counted at a cut-off, the time between passes not in it."""

from benchmark.passes import rate

LAYER = "end to end"
MOVES = None


def read(r):
    return rate(r.passes)
