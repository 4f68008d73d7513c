"""Timeouts that moved a state machine (propose, prevote-wait,
precommit-wait; any node) per 100 heights node 0 committed inside the
window. The program marks each with a `cs:timeout` instant, after the
stale check; the instants that began inside the window are counted."""

from benchmark import program_spans, readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    fired = program_spans.count(r, "cs:timeout")
    heights = len(readers.window_blocks(r))
    if fired is None or not heights:
        return None
    return 100.0 * fired / heights
