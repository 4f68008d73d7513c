"""Mean number of parts of a block of the window (node 0's block
store): a block of 1 KB records is the first in any cell that does not
fit one 64 KB part."""

from benchmark import readers

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    parts = [b["parts"] for b in readers.window_blocks(r) if "parts" in b]
    return sum(parts) / len(parts) if parts else None
