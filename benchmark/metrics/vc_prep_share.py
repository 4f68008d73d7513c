"""Share of the passes' time in the verifier's host prep of a commit
(`verify.prep`: SHA-512 and mod L of 10,000 distinct messages)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.prep")
