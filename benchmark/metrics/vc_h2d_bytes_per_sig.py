"""`lite_h2d_bytes_per_sig` in the single-commit cell, where it moves
`commits_per_s`: the tail chunk's 240 padded lanes are sent too. A
reading of the whole run (bytes and signatures of the warm pass and of
`correct`'s batches after the window included), as its namesake's."""

from benchmark.metrics.lite_h2d_bytes_per_sig import (  # noqa: F401
    LAYER, read)

MOVES = "commits_per_s"
