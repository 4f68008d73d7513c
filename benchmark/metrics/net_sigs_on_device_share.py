"""Share of all signatures the process verified, from boot to the audit,
that went to the device: live votes sit under auto_threshold, the
audit does not."""

from benchmark import readers

LAYER = "verifier"
MOVES = "commit_p50_ms"


def read(r):
    return readers.counter_share(r, "verifier.jax_sigs", "verifier.sigs")
