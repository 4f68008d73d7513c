"""Median time a node spends making a height durable (`cs:commit.persist`,
all nodes): the group flush of the height's store writes and the WAL's
one fsync on the pipelined path, `save_block` and `save_end_height` on
the serial one; inside `cs_commit_p50_ms`."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.per_request_p50_ms(r, "cs:commit.persist")
