"""`lite_predecomp_reuse_share` in the single-commit cell, where it
moves `commits_per_s`: the key-sequence memo at its costly end, 8,192
distinct keys a chunk. A reading of the whole run, as its namesake's:
the counter has no window, so the warm pass's two first sightings and
the wrong-key batches of `correct` after the window are in it."""

from benchmark.metrics.lite_predecomp_reuse_share import (  # noqa: F401
    LAYER, read)

MOVES = "commits_per_s"
