"""Of the window's nil prevotes (`nil_prevotes_per_100_heights`), the share
whose `why` is `no_proposal`: the propose timeout fired and no proposal
had come, as against a proposal whose block never completed (`no_block`)
or did not validate (`invalid_block`). 0 where the window had no nil
prevote at all (`nil_prevotes_per_100_heights` reads 0 beside it): a cell
that lists the metric has to report it in every traced run, and most
windows lose no round. Left out only where the program records no
`cs:nil_vote` (a parent commit) or the ring lost some."""

from benchmark.metrics import nil_prevotes_per_100_heights
from benchmark.stats import share

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    got = nil_prevotes_per_100_heights.nil_prevotes(r)
    if got is None:
        return None
    if not got:
        return 0.0
    return share(sum(1 for row in got
                     if row["args"].get("why") == "no_proposal"), len(got))
