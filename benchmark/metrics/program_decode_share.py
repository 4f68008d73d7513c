"""Share of the passes' time decoding blocks from wire bytes, from the
program's own span (`wire.decode_block`, inside Block.from_bytes and
where a node decodes a block_response); `wire_decode_share` times the
same call from outside."""

from benchmark import program_spans

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "wire.decode_block")
