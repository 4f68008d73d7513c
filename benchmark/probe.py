"""Reading the program's counters and tapping its verifier, from the
benchmark's side: nothing in the program is edited.

`counters()` flattens what the program already counts
(BatchVerifier.stats, ops/ed25519.predecomp_stats(), the Merkle
telemetry families, the chunk-occupancy histogram) into one dict;
`delta` subtracts two of them. `VerifierTap` shadows one verifier
object's `verify_async` for the length of a `with`: it times every
dispatch (`verify_dispatch` span: host prep and enqueue) and every
dispatch-to-verdict interval (`verify_wall`), and, for the control
runs alone, can weaken the verifier the way a tempting later change
would."""

from __future__ import annotations

import time
from typing import Dict, Optional

KERNELS = ("pallas_full", "pallas_pre", "jnp_full", "jnp_pre", "mesh_jnp",
           "decompress", "sign_pallas", "sign_scalar")
CONTROLS = ("accept_all", "truncate")


def counters(verifier) -> Dict[str, float]:
    from tendermint_tpu import telemetry
    from tendermint_tpu.ops import ed25519
    out: Dict[str, float] = {f"verifier.{k}": v
                             for k, v in dict(verifier.stats).items()}
    pre = ed25519.predecomp_stats()
    for k in KERNELS + ("full", "fill", "hit"):
        out[f"kernel.{k}"] = pre[k]
    for impl in ("native", "host", "mesh"):
        out[f"merkle_roots.{impl}"] = float(
            telemetry.value("merkle_roots_total", {"impl": impl}) or 0)
    occ = telemetry.value("verifier_chunk_occupancy")
    out["occupancy.sum"] = float(occ["sum"]) if occ else 0.0
    out["occupancy.count"] = float(occ["count"]) if occ else 0.0
    return out


def delta(after: Dict[str, float], before: Dict[str, float]) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


class VerifierTap:
    def __init__(self, verifier, spans, control: Optional[str] = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; {CONTROLS}")
        self.verifier, self.spans, self.control = verifier, spans, control

    def __enter__(self):
        import numpy as np
        inner = self.verifier.verify_async      # the bound method
        spans, control = self.spans, self.control
        walls = spans.by_name.setdefault("verify_wall", [])

        def verify_async(items):
            t0 = time.perf_counter()
            n = len(items)
            with spans.span("verify_dispatch"):
                if control == "accept_all":
                    resolve = lambda: np.ones(n, np.bool_)   # noqa: E731
                elif control == "truncate":
                    head = inner(items[:n // 2])
                    resolve = lambda: np.concatenate(       # noqa: E731
                        [head(), np.ones(n - n // 2, np.bool_)])
                else:
                    resolve = inner(items)

            def timed():
                out = resolve()
                walls.append((t0, time.perf_counter()))
                return out
            return timed

        self.verifier.verify_async = verify_async
        return self

    def __exit__(self, *exc):
        del self.verifier.__dict__["verify_async"]
        return False


def union_seconds(intervals, t0: float = float("-inf"),
                  t1: float = float("inf")) -> float:
    """Seconds covered by the union of [a, b] intervals, clipped."""
    total, edge = 0.0, None
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if b <= a:
            continue
        if edge is None or a > edge:
            total += b - a
            edge = b
        elif b > edge:
            total += b - edge
            edge = b
    return total
