"""Median number of signatures one `VoteSet.add_vote` call sent to the
verifier inside the window (`cs:vote_ingest`'s `sigs`, over the calls
that sent any): how far live votes sit under `auto_threshold`."""

from benchmark import program_spans
from benchmark.stats import percentile

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    rows = program_spans.rows(r, "cs:vote_ingest")
    if rows is None:
        return None
    t0, t1 = r.window
    sigs = sorted(row["args"].get("sigs", 0) for row in rows
                  if t0 <= row["start"] <= t1)
    return percentile([s for s in sigs if s > 0], 0.5)
