"""`lite_collect_share`'s reading in `chain_100v_churn.lite_follow`: the
share of the passes' time collecting a window (`lite.collect`: the sets
it hands over hashed, the headers, the commits' columns). An entry of
its own because a test holds the `lite_` entry's `workloads` to the
constant-set cell alone."""

from benchmark.metrics.lite_collect_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
