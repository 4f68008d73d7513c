"""Median time the proposer of a round spends handing its parts (and on the
serial path the proposal) to its own queue and to `_broadcast`
(`cs:propose.send`); like `cs_propose_build_p50_ms`, before its PROPOSE
step opens."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:propose.send")
