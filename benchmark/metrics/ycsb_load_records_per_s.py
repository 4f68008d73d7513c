"""Records a second a validator's bulk build loaded (`tree.load`
spans: their `records` over their seconds, all validators together)."""

LAYER = "state tree and read path"
MOVES = "setup_s"


def read(r):
    loads = r.client.get("tree_loads") or ()
    secs = sum(row["seconds"] for row in loads)
    return sum(row["records"] for row in loads) / secs if secs else None
