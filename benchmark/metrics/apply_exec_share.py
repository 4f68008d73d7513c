"""Share of the passes' time executing blocks on the app (`apply.exec`:
BeginBlock, the DeliverTx batch, EndBlock)."""

from benchmark import program_spans

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "apply.exec")
