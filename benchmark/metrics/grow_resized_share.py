"""Share of the blocks the passes applied whose commit was of another
size than the validator set their window was collected with, and came
through the window's pooled batch all the same, paired by address
(`tm_sync_resized_total` over `tm_sync_commits_total`, batched +
reverified): how much of the chain lies above a join or a leave its
window had not seen. Taken between the window's start and its end where
the cell's driver counts it there (`sync_grow`); a whole-run reading of
the two families where it does not (`sync_join`, on whose chain the set
stays at its cap and every run reads 0). Nothing to read where the
program has no such counter (a parent commit) or counted no block."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "sync window engine"
MOVES = "commits_per_s"

_APPLIED = ("sync_commits_total.batched", "sync_commits_total.reverified")


def read(r):
    if "sync_resized_total" in r.counters:
        return share(r.counters["sync_resized_total"],
                     sum(r.counters[k] for k in _APPLIED))
    resized = program_spans.counter_total("sync_resized_total")
    if resized is None:
        return None
    from tendermint_tpu import telemetry
    return share(resized, sum(
        float(telemetry.value("sync_commits_total", {"how": how}) or 0.0)
        for how in ("batched", "reverified")))
