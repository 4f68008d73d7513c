"""Mean milliseconds a finished `broadcast_tx_sync` waited for the loop
thread to take its reply (`tm_rpc_reply_seconds{route}`: from the
handler's return on its worker to `_complete` on the loop, which every
socket of the process's nodes shares). A whole-run reading."""

from benchmark import program_counters

LAYER = "front door"
MOVES = "commit_p50_ms"


def read(r):
    return program_counters.mean_ms(
        "rpc_reply_seconds", {"route": "broadcast_tx_sync"})
