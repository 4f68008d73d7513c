"""p99 of the same client-side latency: the slowest one or two blocks of
the window, so a maximum more than a percentile; reported, never
bounded."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return readers.client_percentile(r, "commit_ms", 0.99)
