"""Share of the passes' time validating blocks before they are applied
(`apply.validate`: validate_block, which hashes the block's transactions
again to hold them to the header's data hash)."""

from benchmark import program_spans

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "apply.validate")
