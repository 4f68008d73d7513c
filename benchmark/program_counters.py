"""Readings of the program's labelled histograms
(tendermint_tpu/telemetry/registry.py) that several metric readers
share. Each is the family as it stands when the reader runs, so a
whole-run reading: it counts since telemetry was switched on, which in
the net cells is the process's start. None where the program has no
such family (a parent commit) or the family has seen nothing."""

from __future__ import annotations

from typing import Optional


def mean_ms(name: str, labels: dict) -> Optional[float]:
    """A histogram child's sum over its count, in milliseconds."""
    from tendermint_tpu import telemetry
    if telemetry.REGISTRY.get(name) is None:
        return None
    doc = telemetry.value(name, labels)
    if not doc or not doc["count"]:
        return None
    return 1000.0 * doc["sum"] / doc["count"]
