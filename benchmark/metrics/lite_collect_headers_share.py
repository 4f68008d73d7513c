"""Share of the passes' time the certifier spends on a window's headers
(`lite.headers`, the first pass of `lite.collect`: the header hash of
`validate_basic` and the valset-hash compare, per header), from the
program's own span, one event a window."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "lite.headers")
