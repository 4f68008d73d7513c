"""Median time a node that is not the round's proposer waits in PROPOSE,
the proposal accepted, for the last part of its block
(`cs:propose.await_block`: to the part set's completion, its `parts` the
set's size)."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:propose.await_block")
