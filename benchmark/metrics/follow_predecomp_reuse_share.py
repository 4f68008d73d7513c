"""`lite_predecomp_reuse_share`'s reading in
`chain_100v_churn.lite_follow`: the share of the chunks whose key rows
came from the memo of whole key sequences (a whole-run counter). An
entry of its own because a test holds the `lite_` entry's `workloads` to
the constant-set cell alone."""

from benchmark.metrics.lite_predecomp_reuse_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
