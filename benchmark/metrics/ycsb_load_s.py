"""Seconds the four validators spent loading the genesis' records into
their trees at InitChain (`tree.load` spans, summed, as the driver read
them when the net had booted): part of `setup_s`."""

LAYER = "state tree and read path"
MOVES = "setup_s"


def read(r):
    loads = r.client.get("tree_loads")
    return sum(row["seconds"] for row in loads) if loads else None
