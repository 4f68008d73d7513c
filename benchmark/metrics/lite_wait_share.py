"""Share of the passes' time the certifier's thread blocks on a window's
verdicts (`lite.wait`): the device, or the fetch, is what it waits for."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "lite.wait")
