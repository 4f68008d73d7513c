"""Median of the CheckTx-to-proposal leg of telemetry/slo.py: how long an
admitted write waits for a block to carry it; traced run only."""

from benchmark import readers

LAYER = "mempool and propose wait"
MOVES = "commit_p50_ms"


def read(r):
    return readers.slo_stage_ms(r, "propose")
