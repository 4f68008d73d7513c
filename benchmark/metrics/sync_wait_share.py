"""Share of the passes' time the sync loop blocks on a window's verdicts
(`sync.wait`)."""

from benchmark import program_spans

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.wait")
