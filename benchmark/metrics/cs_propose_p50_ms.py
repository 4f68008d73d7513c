"""Median time a node spends in PROPOSE per height (`cs:PROPOSE`, all
nodes): until the proposal and its block are complete, or the propose
timeout."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:PROPOSE")
