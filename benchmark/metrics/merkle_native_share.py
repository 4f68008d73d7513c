"""Share of the window's host Merkle roots served by the native builder
(tm_merkle_roots_total{impl}); counted in the traced run, where
telemetry is on."""

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    total = sum(r.counters.get(f"merkle_roots.{i}", 0.0)
                for i in ("native", "host", "mesh"))
    if total <= 0:
        return None
    return 100.0 * r.counters["merkle_roots.native"] / total
