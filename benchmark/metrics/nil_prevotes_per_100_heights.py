"""Nil prevotes the nodes signed (`cs:nil_vote` instants with `type`
prevote that began inside the window, any node) per 100 heights node 0
committed there, as `timeouts_per_100_heights` counts: a node prevotes
nil when its PROPOSE step ends without a block it can vote for, and the
instant's `why` says what it was short of."""

from benchmark import program_spans, readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def nil_prevotes(r):
    """The window's `cs:nil_vote` rows of type prevote; None where the
    program records none or the ring lost some."""
    got = program_spans.rows(r, "cs:nil_vote")
    if got is None:
        return None
    t0, t1 = r.window
    return [row for row in got if t0 <= row["start"] <= t1 and
            row["args"].get("type") == "prevote"]


def read(r):
    got = nil_prevotes(r)
    heights = len(readers.window_blocks(r))
    if got is None or not heights:
        return None
    return 100.0 * len(got) / heights
