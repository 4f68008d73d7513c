"""Median milliseconds of one `StateTree.commit` (`tree.commit` spans
that began inside the window; each of the four validators commits each
block once): the dirty leaves and the paths above them rehashed."""

from benchmark import ycsb_spans

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return ycsb_spans.median_ms(r, "tree.commit")
