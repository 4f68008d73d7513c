"""Milliseconds per block inside BlockchainReactor._apply_window (check
verdicts, store, ABCI apply, commit), from the harness span around the
call."""

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    blocks = sum(p.work - p.failed for p in r.passes)
    if not blocks or not r.spans.count("apply"):
        return None
    return 1000.0 * r.spans.total("apply", *r.window) / blocks
