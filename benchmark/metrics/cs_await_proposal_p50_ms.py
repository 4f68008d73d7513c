"""Median time a node that is not the round's proposer waits in PROPOSE for
the proposal (`cs:propose.await_proposal`: from the step's start to
`_set_proposal`'s acceptance; 0 where the proposal came first). With
`cs_await_block_p50_ms` it adds up to such a node's PROPOSE step; a step
that ends in its timeout records neither."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:propose.await_proposal")
