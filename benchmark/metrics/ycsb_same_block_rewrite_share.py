"""Of the updates in the blocks of the window, the share whose key
another update of the same block also wrote (the block log): what the
skew does to a block. Each of them is delivered and gossiped, and only
the last one's value is hashed."""

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return r.client.get("same_block_rewrite_share")
