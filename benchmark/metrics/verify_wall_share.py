"""Share of the passes' time during which a signature batch was between
dispatch and verdict (union of the verify_async-to-resolve intervals
the tap records)."""

from benchmark import readers

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return readers.verify_wall_share(r)
