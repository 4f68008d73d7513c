"""Seconds of tracing, lowering and backend compile (or cache load) JAX
reported before the window opened."""

LAYER = "compile"
MOVES = "commits_per_s"


def read(r):
    return r.setup_compile_s
