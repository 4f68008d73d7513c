"""Where do the state tree's SHA-256 waves belong: on the device
(ops/sha256.hash_fixed_jit through ops/merkle.sha256_many_device), in
the native batch kernel, where ops/merkle.sha256_many_host sends them,
or in a hashlib loop?

    python scripts/sha_waves.py [--rows 512,4096] [--lengths 1000,65,67]

Times each way at each payload length and wave size, the device's first
call (trace, lower, compile or cache load, first transfer) apart from
its steady calls, and prints one JSON line a case and a summary. Run it
where the device is the one in question (`chiprun -- python
scripts/sha_waves.py`); on a CPU backend the "device" is XLA:CPU."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def best(fn, reps: int) -> float:
    out = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out = min(out, time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="512,4096")
    ap.add_argument("--lengths", default="1000,65,67")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)
    from tendermint_tpu import native
    from tendermint_tpu.ops import merkle
    from tendermint_tpu.utils import compile_cache
    compile_cache.enable()
    import jax
    rng = random.Random(35)
    sha = hashlib.sha256
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "cores": len(os.sched_getaffinity(0))}), flush=True)
    for length in (int(x) for x in args.lengths.split(",")):
        for rows in (int(x) for x in args.rows.split(",")):
            wave = [rng.randbytes(length) for _ in range(rows)]
            want = [sha(p).digest() for p in wave]
            t0 = time.perf_counter()
            got = merkle.sha256_many_device(wave)
            first = time.perf_counter() - t0
            assert got == want
            assert native.sha256_batch(wave) == want
            device = best(lambda: merkle.sha256_many_device(wave),
                          args.reps)
            nat = best(lambda: native.sha256_batch(wave), args.reps)
            lib = best(lambda: [sha(p).digest() for p in wave], args.reps)
            print(json.dumps({
                "length": length, "rows": rows,
                "device_first_call_s": round(first, 4),
                "device_us_per_payload": round(1e6 * device / rows, 3),
                "native_us_per_payload": round(1e6 * nat / rows, 3),
                "hashlib_us_per_payload": round(1e6 * lib / rows, 3),
                # waves after which the device's first call is paid back
                "device_breaks_even_after_waves": None if device >= nat
                else round(first / (nat - device), 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
