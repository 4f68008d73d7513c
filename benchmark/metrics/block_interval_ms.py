"""Median time between consecutive commits at node 0 inside the window."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return readers.block_interval_ms(r)
