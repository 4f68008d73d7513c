"""Median time a node spends validating the block it is about to commit,
per height (`cs:commit.validate`: `validate_block` in
`_finalize_commit`, all nodes); inside `cs_commit_p50_ms`."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:commit.validate")
