"""Share of the passes' time tallying verdicts against the stake
(`lite.check`: check_commit_results per header of a window)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "lite.check")
