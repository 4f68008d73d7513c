"""From a profiler trace to numbers: device busy and idle seconds,
seconds by device operation, and the longest idle gaps named after the
harness span that was open on the host at the time.

Two steps, so that the arithmetic can be checked without a chip:
`read_xplane` turns an `.xplane.pb` into plain events (needs only
JAX's own reader), `reduce_events` does the rest on plain data.

A device's busy time is the UNION of the intervals in which an
operation ran on it (overlapping lines of one plane, such as modules
and the ops inside them, count once). The traced window is the
`bench:profile` span the harness wraps around the profiled part, on
the trace's own clock; without it, the extent of the events.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from benchmark.spans import PREFIX

Event = Tuple[str, int, int]        # name, start_ns, duration_ns
WINDOW_SPAN = "profile"
OPS_LINE = "XLA Ops"


def find_xplanes(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))


def read_xplane(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """{"devices": {plane name: {line name: [Event]}},
        "host_spans": [Event] (the harness's own, prefix stripped)}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host_spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (op_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)) for ev in line.events)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host_spans.append((ev.name[len(PREFIX):],
                                           int(ev.start_ns),
                                           int(ev.duration_ns)))
    return {"devices": devices, "host_spans": host_spans}


def op_name(text: str) -> str:
    """An operation's name from what the trace carries, which on a TPU
    is the whole HLO instruction: `%fusion.1 = s32[...] fusion(...)`."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _owner(spans: List[Event], at: int) -> str:
    """The innermost harness span open at `at` (the shortest one that
    covers it), the window's own span aside."""
    best: Optional[Event] = None
    for ev in spans:
        name, start, dur = ev
        if name != WINDOW_SPAN and start <= at < start + dur and \
                (best is None or dur < best[2]):
            best = ev
    return best[0] if best else "unattributed"


def reduce_events(events: dict, top: int = 10) -> Optional[dict]:
    """None when the trace has neither a device plane nor the harness's
    window span. A traced part in which the device did nothing has no
    device plane at all: it still counts, as a window with nothing busy
    in it. Seconds throughout; `busy_s` is averaged over the device
    planes."""
    devices = events.get("devices") or {}
    spans = events.get("host_spans") or []
    windows = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN and d > 0]
    every = [(s, s + d) for lines in devices.values()
             for evs in lines.values() for _n, s, d in evs]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
        window_ns = sum(b - a for a, b in union(windows))
    elif every:
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
        window_ns = hi - lo
    else:
        return None
    inside = union(windows) if windows else [(lo, hi)]

    busy_ns_total = 0
    by_op: Dict[str, int] = {}
    gaps: List[Tuple[int, int]] = []
    if not devices:
        gaps.extend(inside)
    for lines in devices.values():
        busy: List[Tuple[int, int]] = []
        for w_lo, w_hi in inside:
            merged = union(_clip([(s, s + d) for evs in lines.values()
                                  for _n, s, d in evs], w_lo, w_hi))
            busy.extend(merged)
            edge = w_lo
            for a, b in merged:
                if a > edge:
                    gaps.append((edge, a))
                edge = b
            if w_hi > edge:
                gaps.append((edge, w_hi))
        busy_ns_total += sum(b - a for a, b in busy)
        ops = lines.get(OPS_LINE)
        if ops is None:         # a layout this reader has not seen
            ops = [ev for evs in lines.values() for ev in evs]
        for name, s, d in ops:
            for w_lo, w_hi in inside:
                cut = min(s + d, w_hi) - max(s, w_lo)
                if cut > 0:
                    by_op[name] = by_op.get(name, 0) + cut
    n_dev = max(1, len(devices))
    busy_s = busy_ns_total / n_dev / 1e9
    window_s = window_ns / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "op_seconds": {k: v / n_dev / 1e9 for k, v in by_op.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_owner(spans, (a + b) // 2), (b - a) / 1e9]
                      for a, b in gaps[:top]],
        "devices": len(devices),
    }


def merge(reduced: List[dict], top: int = 10) -> Optional[dict]:
    """Several profiled parts of one run (a net cell traces part of its
    window and its audit) read as one: seconds add, lists merge."""
    reduced = [r for r in reduced if r]
    if not reduced:
        return None
    busy = sum(r["busy_s"] for r in reduced)
    window = sum(r["window_s"] for r in reduced)
    ops: Dict[str, float] = {}
    for r in reduced:
        for k, v in r["op_seconds"].items():
            ops[k] = ops.get(k, 0.0) + v
    gaps = sorted((g for r in reduced for g in r["idle_gaps"]),
                  key=lambda g: -g[1])
    return {"busy_s": busy, "window_s": window,
            "idle_share": 1.0 - busy / window if window > 0 else None,
            "op_seconds": ops,
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": gaps[:top],
            "devices": max(r["devices"] for r in reduced)}


def kernel_seconds(reduced: Optional[dict], marker: str = "pallas") -> float:
    """Seconds of the device operations whose name carries `marker`:
    the program's Pallas kernels by the names the trace gives them."""
    if not reduced:
        return 0.0
    return sum(v for k, v in reduced["op_seconds"].items() if marker in k)
