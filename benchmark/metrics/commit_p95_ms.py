"""p95 of the client-side commit latency over the writes due inside the
window. A per-layer metric and not an end-to-end one: about once in 80
seconds under this load a height needs a second round (a 3.8-5 s gap
between blocks, PERF.md section 6), and whether one falls inside the
window decides this number (720 ms without, 1,400-2,100 ms with), so
no bound on it can hold. The unanswered writes `failed` counts are
not in it."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return readers.client_percentile(r, "commit_ms", 0.95)
