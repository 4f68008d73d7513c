"""`lite_prep_share`'s reading in `chain_100v_churn.lite_follow`: the share
of the passes' time in the verifier's host prep (`verify.prep`). An
entry of its own because a test holds the `lite_` entry's `workloads` to
the constant-set cell alone."""

from benchmark.metrics.lite_prep_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
