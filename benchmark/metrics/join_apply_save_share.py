"""`apply_save_share`'s reading in `chain_100v_join.fastsync_churn`: the same
reader over that cell's passes. An entry of its own because a test holds
the accepted entry's `workloads` to the constant-set cell alone."""

from benchmark.metrics.apply_save_share import LAYER, read  # noqa: F401

MOVES = "commits_per_s"
