"""`vc_collect_share`'s reading in `chain_100v_join.fastsync_churn`: the same
reader over that cell's passes, where `commit.collect` fires only inside
`sync.reverify` (the synchronous `verify_commit` of a block whose pooled
verdicts were thrown away), so the three split `join_reverify_share`. An
entry of its own because a test holds the accepted entry's `workloads`
to the single-commit cell alone."""

from benchmark.metrics.vc_collect_share import LAYER, read  # noqa: F401

MOVES = "commits_per_s"
