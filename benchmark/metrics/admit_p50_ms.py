"""Median of the admit-to-CheckTx leg of telemetry/slo.py (the front
door's batcher and the mempool's CheckTx); stamped in the traced run
only."""

from benchmark import readers

LAYER = "front door"
MOVES = "commit_p50_ms"


def read(r):
    return readers.slo_stage_ms(r, "checktx")
