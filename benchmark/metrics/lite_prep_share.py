"""Share of the passes' time in the verifier's host prep (`verify.prep`:
SHA-512 and mod L of every signature, native, GIL released)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.prep")
