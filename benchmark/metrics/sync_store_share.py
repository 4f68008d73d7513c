"""Share of the passes' time saving blocks (`sync.store`:
block_store.save_block per block, inside the window apply)."""

from benchmark import program_spans

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.store")
