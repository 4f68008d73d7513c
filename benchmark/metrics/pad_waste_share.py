"""Share of the dispatched device lanes that were padding: 1 minus the
mean of the verifier_chunk_occupancy histogram over the window (traced
run, telemetry on)."""

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    n = r.counters.get("occupancy.count", 0.0)
    if n <= 0:
        return None
    return 100.0 * (1.0 - r.counters["occupancy.sum"] / n)
