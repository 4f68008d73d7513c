"""Share of the passes' time the certifier spends turning a window's
commits into one batch of columns (`lite.votes`, the second pass of
`lite.collect`: the vote walk of `commit_verification_items` per commit
and the `concat`), from the program's own span, one event a window."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "lite.votes")
