"""Median time a node spends in PRECOMMIT and PRECOMMIT_WAIT per height
(`cs:PRECOMMIT` + `cs:PRECOMMIT_WAIT`, all nodes): until +2/3
precommits."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(
        r, "cs:PRECOMMIT", "cs:PRECOMMIT_WAIT")
