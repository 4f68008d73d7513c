"""Share of the blocks the passes applied whose commit the window
engine judged a second time: one synchronous `verify_commit` under the
live set, because the validator set had moved since the block's window
was collected and its pooled verdicts were thrown away
(`tm_sync_commits_total`, how="reverified" over reverified + batched,
between the window's start and its end). 0 on a chain whose set never
moves. Nothing to read where the program has no such counter (a parent
commit) or counted no block."""

from benchmark.stats import share

LAYER = "sync window engine"
MOVES = "commits_per_s"


def read(r):
    again = r.counters.get("sync_commits_total.reverified")
    if again is None:
        return None
    return share(again, again + r.counters["sync_commits_total.batched"])
