"""Share of the window the cyclic collector held the interpreter that all
the in-process validators share, for 1 ms or more at a time (`gc.collect`
events clipped to the window, as `sync_gc_pause_share` clips them to the
passes; a shorter collection leaves no event and is not in it). Set-up's
collections, the harness's own `gc.collect()` before the window among
them, fall outside."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "host runtime"
MOVES = "commit_p50_ms"


def read(r):
    paused = program_spans.seconds(r, ("gc.collect",))
    if paused is None:
        return None
    return share(paused, r.window[1] - r.window[0])
