"""Episodes of a bounded queue over 80% full that the watcher reported
inside the window (`queue.saturated` instants, one an episode and kind,
from `telemetry/queues._fire`; args `queue`, `depth`): on the recorder's
clock, so that a full send queue can be held against the round it may
have cost."""

from benchmark import program_spans

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    return program_spans.count(r, "queue.saturated")
