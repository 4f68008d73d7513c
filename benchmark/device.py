"""The device the run is on, as JAX reports it. The command refuses to
measure unless every device is a TPU and there are as many as the cell
asks for; it never falls back."""

from __future__ import annotations

import json
import os
from typing import Optional


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if any(d.platform != "tpu" for d in devs) or len(devs) < chips:
        found = sorted({f"{d.platform} ({d.device_kind})" for d in devs})
        raise NoChip(
            f"needs {chips} TPU chip(s) and a TPU on every device; JAX "
            f"found {len(devs)} device(s): {', '.join(found)} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
            "Nothing was measured.")
    info = describe()
    peaks(info["kind"])     # an unknown device is an error
    return info


def describe() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax
    peaks_ = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def peaks(kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise NoChip(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]
