"""Median number of leaves one `StateTree.commit` of the window
rehashed (`tree.commit`'s `dirty_leaves`): a block's updates less the
keys it wrote more than once."""

from benchmark import ycsb_spans

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return ycsb_spans.median_arg(r, "tree.commit", "dirty_leaves")
