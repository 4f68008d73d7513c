"""Median time the proposer of a round spends making its proposal
(`cs:propose.build`): the mempool's reap, the block (or the precomputed
one), its bytes, the part set and the signature. The proposer does this
before its own PROPOSE step opens, so it lies in that node's NEW_HEIGHT
time, and in every other node's wait for the proposal."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:propose.build")
