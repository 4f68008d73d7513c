"""The longest time between two consecutive commits at node 0 inside the
window: some 600 ms when every height took one round, 3.8 s and more
when one needed a second (timeout_propose is 3 s)."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    at = [b["seen_at"] for b in readers.window_blocks(r)]
    return max((1000.0 * (b - a) for a, b in zip(at, at[1:])), default=None)
