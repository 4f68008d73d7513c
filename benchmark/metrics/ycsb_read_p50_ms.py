"""Median milliseconds from a proven read's due time to its reply at the
client, over the reads due inside the window."""

from benchmark import readers

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return readers.client_percentile(r, "read_ms", 0.50)
