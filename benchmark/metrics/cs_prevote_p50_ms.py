"""Median time a node spends in PREVOTE and PREVOTE_WAIT per height
(`cs:PREVOTE` + `cs:PREVOTE_WAIT`, all nodes): until +2/3 prevotes."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(
        r, "cs:PREVOTE", "cs:PREVOTE_WAIT")
