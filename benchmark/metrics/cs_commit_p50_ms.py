"""Median time a node spends in COMMIT per height (`cs:COMMIT`, all nodes):
from +2/3 precommits to the next height's state, which is the block
validated, staged, applied, flushed, the WAL's fsync and the hooks. What
the four step metrics leave of a block interval."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:COMMIT")
