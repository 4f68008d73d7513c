"""Median time from a write's DUE time to the arrival of its commit event
at the client, over the writes due inside the window."""

from benchmark import readers

LAYER = "end to end"
MOVES = None


def read(r):
    return readers.client_percentile(r, "commit_ms", 0.50)
