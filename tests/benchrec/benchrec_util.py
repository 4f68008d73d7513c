"""Shared by the benchmark's own tests."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(workload: str, seed: int = 7, seconds: float = 0.5,
             trace: bool = False, **overrides) -> dict:
    """One run of the whole command's path at toy sizes on the CPU:
    what the test suite may do and the command never does."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.utils.log import setup_logging
    from benchmark.harness import run_cell
    setup_logging("error")
    was = telemetry.enabled()
    try:
        return run_cell(REPO, workload, seed, seconds, trace, rehearsal=True,
                        overrides=overrides)
    finally:
        # a driver switches telemetry to its --trace for the life of its
        # process; here the process goes on to other tests
        telemetry.set_enabled(was)
