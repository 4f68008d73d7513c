"""`program_decode_share`'s reading in `chain_100v_join.fastsync_churn`: the same
reader over that cell's passes. An entry of its own because a test holds
the accepted entry's `workloads` to the constant-set cell alone."""

from benchmark.metrics.program_decode_share import LAYER, read  # noqa: F401

MOVES = "commits_per_s"
