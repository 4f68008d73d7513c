"""Backend compiles (or cache loads) JAX reported inside the measured
window; a run that saw one exits non-zero, so a line carries 0."""

LAYER = "compile"
MOVES = "commits_per_s"


def read(r):
    return float(r.compiles_in_window)
