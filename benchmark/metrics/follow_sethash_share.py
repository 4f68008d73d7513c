"""Share of the passes' time hashing the validator sets a window's
headers hand over (`lite.sethash`, the first part of `lite.headers`,
one event a window: a Merkle root over one leaf a validator for every
distinct set object; a constant set is hashed once a pass, a set that
moves every few headers a thousand times). Nothing to read where the
batch path dispatched no window (`lite_windows_total` stands at 0)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"


def read(r):
    if not program_spans.counter_total("lite_windows_total"):
        return None
    return program_spans.share_of_passes(r, "lite.sethash")
