"""Share of the passes' time saving the ABCI responses and the state
(`apply.save`, twice a block)."""

from benchmark import program_spans

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "apply.save")
