"""Arithmetic shared by the metric readers (metrics/<name>.py): each
reader is a few lines over a Reading; what several of them need is
here once."""

from __future__ import annotations

from typing import List, Optional

from benchmark import probe
from benchmark.stats import percentile, share
from benchmark.trace_reduce import kernel_seconds


def pass_seconds(r) -> float:
    return sum(p.seconds for p in r.passes)


def span_share_of_passes(r, name: str) -> Optional[float]:
    """Seconds of span `name` inside the window, as a share (%) of the
    whole passes' own seconds."""
    if not r.passes or not r.spans.count(name):
        return None
    return share(r.spans.total(name, *r.window), pass_seconds(r))


def verify_wall_share(r) -> Optional[float]:
    walls = r.spans.by_name.get("verify_wall")
    if not r.passes or not walls:
        return None
    return share(probe.union_seconds(walls, *r.window), pass_seconds(r))


def counter_share(r, part: str, whole: str) -> Optional[float]:
    if whole not in r.counters:
        return None
    return share(r.counters.get(part, 0.0), r.counters[whole])


def kernel_busy_share(r) -> Optional[float]:
    if not r.trace:
        return None
    return share(kernel_seconds(r.trace), r.trace["window_s"])


def kernel_sigs_per_s(r) -> Optional[float]:
    if not r.trace:
        return None
    secs = kernel_seconds(r.trace)
    sigs = r.counters.get("profiled_jax_sigs", 0.0)
    return sigs / secs if secs > 0 and sigs > 0 else None


def device_idle_share(r) -> Optional[float]:
    if not r.trace or r.trace["idle_share"] is None:
        return None
    return 100.0 * r.trace["idle_share"]


def client_percentile(r, series: str, p: float) -> Optional[float]:
    return percentile(r.client.get(series) or [], p)


def slo_stage_ms(r, stage: str, q: str = "p50_ms") -> Optional[float]:
    """A leg of telemetry/slo.py's lifecycle, keyed by the stage that
    closes it; stamped in the traced run only."""
    doc = (r.client.get("slo_stages") or {}).get(stage)
    return None if not doc else doc.get(q)


def window_blocks(r) -> List[dict]:
    return r.client.get("blocks") or []


def block_interval_ms(r) -> Optional[float]:
    """Median time between consecutive blocks node 0 committed inside
    the window."""
    at = [b["seen_at"] for b in window_blocks(r)]
    gaps = sorted(1000.0 * (b - a) for a, b in zip(at, at[1:]))
    return percentile(gaps, 0.5)


def txs_per_block(r) -> Optional[float]:
    blocks = window_blocks(r)
    return sum(b["txs"] for b in blocks) / len(blocks) if blocks else None
