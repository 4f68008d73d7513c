"""Milliseconds an in-process validator spends in `VoteSet.add_vote`
with its verify per height of the window (`cs:vote_ingest`, the
program's span; both in-process validators, per thread, divided by
their number and by the heights node 0 committed inside the window)."""

from benchmark import program_spans, readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    secs = program_spans.seconds(r, ("cs:vote_ingest",))
    heights = len(readers.window_blocks(r))
    nodes = r.client.get("in_process")
    if secs is None or not heights or not nodes:
        return None
    return 1000.0 * secs / (heights * nodes)
