"""Share of the passes' time building part sets (`sync.parts`: serialize,
split and Merkle per block, inside the window collect)."""

from benchmark import program_spans

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.parts")
