"""Share of the votes offered to the in-process validators' VoteSets
inside the window that they held already
(`tm_consensus_votes_total{outcome}`: duplicate over all outcomes):
what gossip over a graph sends twice."""

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"
OUTCOMES = ("added", "duplicate", "rejected")


def now():
    """The counter by outcome as it stands; None where the program has
    no such family."""
    from tendermint_tpu import telemetry
    if "consensus_votes_total" not in telemetry.REGISTRY.names():
        return None
    return {o: float(telemetry.value("consensus_votes_total",
                                     {"outcome": o}) or 0.0)
            for o in OUTCOMES}


def read(r):
    a, b = (r.client.get(k, {}).get("votes")
            for k in ("n100_open", "n100_close"))
    if not a or not b:
        return None
    got = {o: b[o] - a[o] for o in OUTCOMES}
    total = sum(got.values())
    return 100.0 * got["duplicate"] / total if total > 0 else None
