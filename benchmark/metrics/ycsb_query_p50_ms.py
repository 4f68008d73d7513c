"""Median milliseconds of a proven read inside the application
(`app.query` spans with `prove` 1 that began inside the window): the
server's side of `ycsb_read_p50_ms`, the tree's lock included."""

from benchmark import ycsb_spans

LAYER = "state tree and read path"
MOVES = "commit_p50_ms"


def read(r):
    return ycsb_spans.median_ms(r, "app.query", lambda a: a.get("prove"))
