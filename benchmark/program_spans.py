"""Per-layer metrics from the spans the program records itself
(tendermint_tpu/telemetry/trace.py): the one ring, read through
`TRACER.between` on the harness's own clock (time.perf_counter).

Every function returns None, and the line leaves the metric out, where
there is nothing sound to read: a program that has no such recorder or
no such span in its catalogue (a parent commit), or a ring that
displaced an event that ended inside the window. A span the catalogue
names but that never ran in the window reads as 0 seconds: that is a
measurement, not a gap.

Spans are clipped to the whole passes where the cell has passes (they
lie inside `Reading.window`), to the window otherwise. Time is the
union of a name's intervals per thread, summed over threads, so nested
or repeated spans never count twice and two resolvers count as two.
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.probe import union_seconds
from benchmark.readers import pass_seconds
from benchmark.stats import percentile, share


def tracer():
    """The program's trace module, or None where it is not this one."""
    try:
        from tendermint_tpu.telemetry import trace
    except ImportError:
        return None
    if not hasattr(trace, "SPANS") or not hasattr(trace.TRACER, "between"):
        return None
    return trace


_noted = set()      # windows whose ring use has been printed


def rows(r, name: str) -> Optional[List[dict]]:
    """The events called `name` that overlap the window, as
    `Tracer.between` gives them. The first read of a window prints how
    full the ring is and what it lost, beside the harness's notes."""
    trace = tracer()
    if trace is None or name not in trace.SPANS or None in r.window:
        return None
    got, dropped = trace.TRACER.between(name, *r.window)
    if tuple(r.window) not in _noted:
        _noted.add(tuple(r.window))
        print(json.dumps({"bench": "ring",
                          "events": len(trace.TRACER.events()),
                          "dropped": trace.TRACER.dropped,
                          "dropped_in_window": dropped}), flush=True)
    return None if dropped else got


def _clips(r) -> List[Tuple[float, float]]:
    if r.passes:
        return [(p.start, p.start + p.seconds) for p in r.passes]
    return [tuple(r.window)]


def seconds(r, names: Iterable[str],
            by_thread: bool = True) -> Optional[float]:
    """Seconds of the spans called `names` inside the passes (or the
    window): per thread and summed, or, with `by_thread` false, the
    time during which any of them was open on any thread."""
    threads: Dict[int, List[Tuple[float, float]]] = {}
    for name in names:
        got = rows(r, name)
        if got is None:
            return None
        for row in got:
            threads.setdefault(row["tid"] if by_thread else 0, []).append(
                (row["start"], row["end"]))
    return sum(union_seconds(spans, lo, hi)
               for spans in threads.values() for lo, hi in _clips(r))


def share_of_passes(r, *names: str) -> Optional[float]:
    """`seconds` as a share (%) of the whole passes' own seconds."""
    if not r.passes:
        return None
    secs = seconds(r, names)
    return None if secs is None else share(secs, pass_seconds(r))


def uncovered_share_of_passes(r, name: str) -> Optional[float]:
    """100 minus the share of the passes' seconds during which a span
    called `name` was open on any thread."""
    if not r.passes:
        return None
    secs = seconds(r, (name,), by_thread=False)
    covered = None if secs is None else share(secs, pass_seconds(r))
    return None if covered is None else 100.0 - covered


def per_request_p50_ms(r, *names: str) -> Optional[float]:
    """Median, over (node, request), of the milliseconds that node
    spent in spans called `names` for that request, over the spans
    that ended inside the window. For the consensus steps a request is
    a height: a step entered twice in a height (a second round) counts
    once, with both stays added."""
    t0, t1 = r.window
    total: Dict[tuple, float] = {}
    for name in names:
        got = rows(r, name)
        if got is None:
            return None
        for row in got:
            if t0 <= row["end"] <= t1 and row["start"] >= t0:
                key = (row["args"].get("node"), row["req"])
                total[key] = total.get(key, 0.0) + \
                    1000.0 * (row["end"] - row["start"])
    return percentile(sorted(total.values()), 0.5)


def count(r, name: str) -> Optional[int]:
    """How many events called `name` began inside the window."""
    got = rows(r, name)
    if got is None:
        return None
    t0, t1 = r.window
    return sum(1 for row in got if t0 <= row["start"] <= t1)


def counter_total(name: str) -> Optional[float]:
    """A counter of the program's registry as it stands now (since
    telemetry was switched on, at the start of a traced run); None
    where the program has no such family."""
    from tendermint_tpu import telemetry
    if name not in telemetry.REGISTRY.names():
        return None
    return float(telemetry.value(name) or 0.0)
