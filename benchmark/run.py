"""python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once in this process and prints the result as the last
line of standard output. Exits non-zero, with no result line, unless
every device JAX reports is a TPU and there are as many as the cell
asks for."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0_ENV = "TPU_BFT_BENCH_T0"


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(prog="python -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one traffic parameter (sweeps only; "
                         "the driver's check never passes it)")
    args = ap.parse_args(argv)

    if os.environ.get("PYTHONHASHSEED") != "0":
        # one environment per process: str and bytes hashes, and so
        # the order of every set and dict of them, are the same in
        # every run
        print("benchmark: re-executing with PYTHONHASHSEED=0", flush=True)
        env = dict(os.environ, PYTHONHASHSEED="0")
        env.setdefault(T0_ENV, repr(time.time()))
        os.execve(sys.executable,
                  [sys.executable, "-m", "benchmark.run"] + argv, env)
    t_start = float(os.environ.get(T0_ENV) or time.time())

    root = os.getcwd()
    if not os.path.isfile(os.path.join(root, "BENCHMARK.json")):
        print("benchmark: run from the root of the checkout "
              "(no BENCHMARK.json here)", file=sys.stderr)
        return 4
    try:
        import tendermint_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 4

    overrides = {}
    for item in args.set:
        key, _, raw = item.partition("=")
        overrides[key] = json.loads(raw)

    from tendermint_tpu.utils import compile_cache
    from tendermint_tpu.utils.log import setup_logging
    from benchmark.device import NoChip
    from benchmark.harness import run_cell
    setup_logging("error")
    try:
        cache_dir = compile_cache.enable()
        print(json.dumps({"bench": "cache", "dir": cache_dir}), flush=True)
        line = run_cell(root, args.workload, args.seed, args.seconds,
                        bool(args.trace), overrides=overrides,
                        t_start=t_start)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
