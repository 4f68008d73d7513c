"""`lite_starved_share` in the single-commit cell, where it moves
`commits_per_s`: 100 minus the union of `verify.inflight`."""

from benchmark.metrics.lite_starved_share import LAYER, read  # noqa: F401

MOVES = "commits_per_s"
