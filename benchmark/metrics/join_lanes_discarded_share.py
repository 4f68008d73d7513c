"""Share of the lanes the verifier judged for the window engine's pooled
batches whose verdicts were thrown away, because the validator set's
hash was no longer the one their window was collected with
(`tm_sync_lanes_total`, how="discarded" over discarded + used, between
the window's start and its end): device work done for nothing. Nothing
to read where the program has no such counter (a parent commit) or
counted no lane."""

from benchmark.stats import share

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    lost = r.counters.get("sync_lanes_total.discarded")
    if lost is None:
        return None
    return share(lost, lost + r.counters["sync_lanes_total.used"])
