"""Mean transactions in the blocks node 0 committed inside the window."""

from benchmark import readers

LAYER = "mempool and propose wait"
MOVES = "commit_p50_ms"


def read(r):
    return readers.txs_per_block(r)
