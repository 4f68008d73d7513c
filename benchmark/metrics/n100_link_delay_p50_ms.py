"""Median milliseconds the in-process validators' delaying links held a
frame inside the window (`tm_p2p_link_delay_seconds`, the histogram's
median by its buckets, interpolated inside the one it falls in)."""

import math

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def now():
    """{upper bound: cumulative count}; None where the program has no
    such histogram."""
    from tendermint_tpu import telemetry
    if "p2p_link_delay_seconds" not in telemetry.REGISTRY.names():
        return None
    doc = telemetry.value("p2p_link_delay_seconds")
    return None if doc is None else dict(doc["buckets"])


def median_of_buckets(a: dict, b: dict):
    """The median of what was observed between two cumulative
    snapshots, in the buckets' unit."""
    uppers = sorted(b)
    cum = [b[u] - a.get(u, 0) for u in uppers]
    if not cum or cum[-1] <= 0:
        return None
    half = cum[-1] / 2.0
    lo, below = 0.0, 0
    for upper, c in zip(uppers, cum):
        if c >= half:
            if math.isinf(upper):
                return lo
            return lo + (upper - lo) * (half - below) / max(1, c - below)
        lo, below = upper, c
    return None


def read(r):
    a, b = (r.client.get(k, {}).get("link_delay")
            for k in ("n100_open", "n100_close"))
    if a is None or b is None:
        return None
    med = median_of_buckets(a, b)
    return None if med is None else 1000.0 * med
