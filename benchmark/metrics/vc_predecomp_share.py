"""Share of the passes' time finding the predecompressed pubkey rows of a
commit's two chunks (`verify.predecomp`), 8,192 and 1,808 distinct keys."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.predecomp")
