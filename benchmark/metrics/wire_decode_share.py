"""Share of the passes' time spent decoding the peer's wire bytes into
blocks (Block.from_bytes), from the harness span around the call."""

from benchmark import readers

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return readers.span_share_of_passes(r, "decode")
