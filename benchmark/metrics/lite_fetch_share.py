"""Share of the passes' time the resolver's thread spends in the blocking
fetch of a dispatch's verdicts (`verify.fetch`); it overlaps the
certifier's own thread."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.fetch")
