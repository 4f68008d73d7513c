"""Share of the passes' time one commit's caller blocks on its verdicts
(`commit.wait`: the finisher in the resolver, `verify.fetch` inside it):
with nothing pooled, the kernels of both chunks are waited for here."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "commit.wait")
