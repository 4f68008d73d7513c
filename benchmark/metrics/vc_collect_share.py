"""Share of the passes' time one commit's caller spends collecting it
(`commit.collect`: the structural checks and the 10,000 sign-byte
triples of `commit_verification_items`), from the program's own span."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "commit.collect")
