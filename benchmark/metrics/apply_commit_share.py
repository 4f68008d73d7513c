"""Share of the passes' time in the app's commit and the mempool update
under the mempool lock (`apply.commit`)."""

from benchmark import program_spans

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "apply.commit")
