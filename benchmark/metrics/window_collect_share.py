"""Share of the passes' time inside BlockchainReactor._collect_window
(part sets, block ids, signature triples), from the harness span
around the call."""

from benchmark import readers

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return readers.span_share_of_passes(r, "collect")
