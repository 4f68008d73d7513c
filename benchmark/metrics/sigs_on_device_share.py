"""Share of the window's signatures that BatchVerifier sent to the device
(stats jax_sigs over sigs)."""

from benchmark import readers

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return readers.counter_share(r, "verifier.jax_sigs", "verifier.sigs")
