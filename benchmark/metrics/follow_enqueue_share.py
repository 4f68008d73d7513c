"""`lite_enqueue_share`'s reading in `chain_100v_churn.lite_follow`: the
share of the passes' time handing chunks to the device
(`verify.enqueue`). An entry of its own because a test holds the `lite_`
entry's `workloads` to the constant-set cell alone."""

from benchmark.metrics.lite_enqueue_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
