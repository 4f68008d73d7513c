"""Share of the window's blocks that needed more than one consensus
round."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    blocks = readers.window_blocks(r)
    if not blocks:
        return None
    return 100.0 * sum(1 for b in blocks if b["round"] > 0) / len(blocks)
