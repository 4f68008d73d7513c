"""How late the load generator sent: p99 of send time minus due time over
the window's writes. A starved generator is not a fast server."""

from benchmark import readers

LAYER = "front door"
MOVES = "commit_p50_ms"


def read(r):
    return readers.client_percentile(r, "late_ms", 0.99)
