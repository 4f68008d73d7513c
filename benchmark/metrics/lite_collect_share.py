"""Share of the passes' time the certifier spends collecting a window
(`lite.collect`: structural checks, valset continuity and the signature
triples of 512 headers), from the program's own span."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "lite.collect")
