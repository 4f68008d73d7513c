"""What does one chunk's `verify.predecomp` cost on this host? The key
table's lookup (ops/ed25519._predecomp_rows, rows by index) against the
path it replaced (PR 25-40: a dict of per-key row tuples, stacked lane
by lane), at the two ends of the scale the benchmark's cells send.

    python scripts/predecomp_lookup.py [--reps 30]

Shapes, 8,192 lanes each: `follow` is a follower's chunk where a key
joined (82 commits over two key lists of 100 that differ in one key,
164 keys resident: chain_100v_churn.lite_follow), `distinct` is 8,192
distinct keys (a chunk of commit_10kv.verify_commit). For each, a key
sequence the memo has not kept (`new`: every chunk of the follow cell)
and one it has (`kept`: every chunk of the other two cells). Every row
the table hands out is checked against the old path's, byte for byte.
One JSON line a case: the best and the median of `reps` calls in ms.
It touches no device (the table's mirror lives on the CPU backend
here); run it on the host in question
(`chiprun -- python scripts/predecomp_lookup.py`)."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import statistics
import sys
import time
from collections import OrderedDict

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 8192


def key(tag: int, i: int) -> bytes:
    return hashlib.sha256(b"predecomp lookup %d.%d" % (tag, i)).digest()


def row_of(k: bytes):
    """A key's row without the sqrt: the cache layer knows nothing of
    the bytes but that they belong to the key."""
    d = hashlib.sha512(k).digest()
    return d[:32], d[32:], bool(d[0] & 1)


def shapes():
    pool = [key(0, i) for i in range(164)]
    before = pool[:100]
    after = before[:37] + [pool[100]] + before[38:]
    return {"follow": (pool, (before * 41 + after * 41)[:LANES]),
            "distinct": ([key(1, i) for i in range(LANES)],) * 2}


class OldPath:
    """The parent's _predecomp_rows without its lock and counters: the
    per-key OrderedDict of 3-tuples, the memo of whole stacked rows."""

    def __init__(self, resident):
        import numpy as np
        self.np = np
        self.rows = OrderedDict()
        for k in resident:
            xn, y, ok = row_of(k)
            self.rows[k] = (np.frombuffer(xn, np.uint8).copy(),
                            np.frombuffer(y, np.uint8).copy(), ok)
        self.memo = OrderedDict()

    def __call__(self, pk):
        np, rows_of = self.np, self.rows
        n = pk.shape[0]
        raw = pk.tobytes()
        memo = self.memo.get(raw)
        if memo is not None:
            distinct, out = memo
            if distinct is None:
                distinct = memo[0] = tuple(reversed(dict.fromkeys(
                    raw[i - 32:i] for i in range(32 * n, 0, -32))))
            if all(map(rows_of.__contains__, distinct)):
                for k in distinct:
                    rows_of.move_to_end(k)
                self.memo.move_to_end(raw)
                return out
        keys = [raw[i * 32:(i + 1) * 32] for i in range(n)]
        rows = [rows_of.get(k) for k in keys]
        assert not {k for k, r in zip(keys, rows) if r is None}
        for k in keys:
            rows_of.move_to_end(k)
        out = (np.stack([r[0] for r in rows]), np.stack([r[1] for r in rows]),
               np.array([r[2] for r in rows], np.bool_))
        self.memo[raw] = [None, out]
        return out


def timed(fn, reps: int, before=None):
    out = []
    for _ in range(reps):
        if before is not None:
            before()
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return round(min(out), 4), round(statistics.median(out), 4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)
    import numpy as np
    from tendermint_tpu.ops import ed25519
    print(json.dumps({"sched_getaffinity": len(os.sched_getaffinity(0)),
                      "cpu_count": os.cpu_count(), "lanes": LANES,
                      "table_slots": ed25519._PREDECOMP_MAX_KEYS}),
          flush=True)
    table, memo = ed25519._predecomp, ed25519._predecomp_memo
    for name, (resident, chunk) in shapes().items():
        pk = np.frombuffer(b"".join(chunk), np.uint8).reshape(LANES, 32)
        for c in (table, memo, ed25519._predecomp_seen):
            c.clear()
        rows = [row_of(k) for k in resident]
        cols = [np.frombuffer(b"".join(r[i] for r in rows),
                              np.uint8).reshape(-1, 32) for i in (0, 1)]
        table.insert(np.frombuffer(b"".join(resident),
                                   np.uint8).reshape(-1, 32),
                     cols[0], cols[1], np.array([r[2] for r in rows]))
        old = OldPath(resident)
        mirror, idx = ed25519._predecomp_rows(pk, None)
        got_rows = np.asarray(mirror)[idx]
        for got, want in zip((got_rows[:, :32], got_rows[:, 32:64],
                              got_rows[:, 64] != 0), old(pk)):
            assert got.dtype == want.dtype
            assert got.tobytes() == want.tobytes(), name
        cases = {
            "old_new": (lambda: old(pk), old.memo.clear),
            "old_kept": (lambda: old(pk), None),
            "table_new": (lambda: ed25519._predecomp_rows(pk, None),
                          memo.clear),
            "table_kept": (lambda: ed25519._predecomp_rows(pk, None), None),
        }
        for case, (fn, before) in cases.items():
            fn()
            best, median = timed(fn, args.reps, before)
            print(json.dumps({"shape": name, "distinct_keys": len(resident),
                              "path": case, "best_ms": best,
                              "median_ms": median}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
