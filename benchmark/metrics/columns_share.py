"""`lite_columns_share` in the cells where it moves `commits_per_s`:
fast-sync's windows of 16,384 signatures and the single commit of
10,000. A reading of the whole run, as its namesake's."""

from benchmark.metrics.lite_columns_share import (  # noqa: F401
    LAYER, read)

MOVES = "commits_per_s"
