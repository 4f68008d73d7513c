"""Share of the passes' time the cyclic collector held the syncing thread
for 1 ms or more at a time (`gc.collect` events clipped to the passes; a
shorter collection leaves no event and is not in it). The driver's own
`gc.collect()` between passes falls outside them."""

from benchmark import program_spans

LAYER = "host runtime"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "gc.collect")
