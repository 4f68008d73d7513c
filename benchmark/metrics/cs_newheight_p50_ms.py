"""Median time a node waits in NEW_HEIGHT per height (`cs:NEW_HEIGHT`,
all nodes, steps that ended inside the window): the commit timeout,
or less when `txs_available` cuts it short."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:NEW_HEIGHT")
