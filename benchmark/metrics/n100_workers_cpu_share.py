"""CPU seconds the worker processes (98 of the hundred validators)
used inside the window, over usable cores times the window: each
worker's own `os.times()`, asked for over its pipe at both ends."""

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return r.client.get("workers_cpu_share")
