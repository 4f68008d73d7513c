"""How does the verify prep's SHA-512 loop (native/prep.cpp hash_lanes)
scale over threads on this host? The table behind the two constants of
models/verifier.prep_threads (MIN_LANES_A_THREAD, MAX_PREP_THREADS).

    python scripts/prep_threads.py [--threads 1,2,4,8] [--reps 15]

Times native.prep_columns at each thread count over the two batches the
benchmark's verifier cells send (10,000 lanes with a 118-byte message
each: one commit of commit_10kv; 32,768 lanes with a message per 64:
one certify window of chain_64v) and over small batches around the
rule's floor, checks every result against one thread's byte for byte,
and prints one JSON line a case: the best and the median of `reps`
calls in ms. It touches no device; run it on the host in question
(`chiprun -- python scripts/prep_threads.py`): the sandbox reports 8
cores and runs on one."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def batch(rng, n: int, lanes_a_msg: int):
    """Columns of n lanes that all pass their prechecks (s < L by a zero
    top byte), so pass 2 hashes every one."""
    import numpy as np
    pk = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    raw = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    raw[:, 63] = 0
    sigs = [row.tobytes() for row in raw]
    n_msgs = -(-n // lanes_a_msg)
    msgs = [rng.integers(0, 256, 118, dtype=np.uint8).tobytes()
            for _ in range(n_msgs)]
    idx = (np.arange(n) // lanes_a_msg).astype(np.int32)
    return pk, sigs, msgs, idx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", default="1,2,3,4,6,8,12")
    ap.add_argument("--reps", type=int, default=15)
    args = ap.parse_args(argv)
    import numpy as np
    from tendermint_tpu import native
    from tendermint_tpu.models import verifier
    try:
        with open("/sys/fs/cgroup/cpu.max") as f:
            quota = f.read().strip()
    except OSError:
        quota = None
    print(json.dumps({
        "sched_getaffinity": len(os.sched_getaffinity(0)),
        "cpu_count": os.cpu_count(), "cgroup_cpu_max": quota,
        "sha512_impl": native._prep().sha512_impl,
        "rule": {str(n): verifier.prep_threads(n)
                 for n in (256, 2_000, 2_048, 8_192, 10_000, 32_768)}}),
        flush=True)
    rng = np.random.default_rng(36)
    threads = [int(t) for t in args.threads.split(",")]
    for n, lanes_a_msg in ((10_000, 1), (32_768, 64), (1_024, 1),
                           (2_048, 1), (4_096, 1), (8_192, 64)):
        cols = batch(rng, n, lanes_a_msg)
        want = [a.tobytes() for a in native.prep_columns(*cols)]
        for t in threads:
            took = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                got = native.prep_columns(*cols, t)
                took.append(time.perf_counter() - t0)
                assert [a.tobytes() for a in got] == want
            print(json.dumps({
                "lanes": n, "lanes_a_msg": lanes_a_msg, "threads": t,
                "best_ms": round(1e3 * min(took), 3),
                "median_ms": round(1e3 * statistics.median(took), 3)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
