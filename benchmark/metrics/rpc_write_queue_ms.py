"""Mean milliseconds an admitted `broadcast_tx_sync` waited for one of the
front door's six worker threads (`tm_rpc_queue_seconds{route}`: from
`_dispatch` on the loop to the first line of the call on a pool thread).
A whole-run reading: warm-up and drain are in it."""

from benchmark import program_counters

LAYER = "front door"
MOVES = "commit_p50_ms"


def read(r):
    return program_counters.mean_ms(
        "rpc_queue_seconds", {"route": "broadcast_tx_sync"})
