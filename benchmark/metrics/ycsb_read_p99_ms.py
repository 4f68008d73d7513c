"""99th percentile of the milliseconds from a proven read's due time to
its reply at the client: where a read that waited for the tree's lock
behind a commit, or in the front door's queue behind a block's worth of
updates, shows."""

from benchmark import readers

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return readers.client_percentile(r, "read_ms", 0.99)
