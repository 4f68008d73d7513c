"""`lite_starved_share`'s reading in `chain_100v_churn.lite_follow`: the
share of the passes' time with nothing of the verifier's queued on the
device (100 minus the union of `verify.inflight`). An entry of its own
because a test holds the `lite_` entry's `workloads` to the constant-set
cell alone."""

from benchmark.metrics.lite_starved_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
