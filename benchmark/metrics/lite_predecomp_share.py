"""Share of the passes' time assembling predecompressed pubkey rows
(`verify.predecomp`: key slicing, cache lookups and row stacking per
chunk, under the cache's lock)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.predecomp")
