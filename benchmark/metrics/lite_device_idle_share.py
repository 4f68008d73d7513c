"""`device_idle_share` in the lite cell: an entry of its own because that
cell's end-to-end metric is `headers_per_s`."""

from benchmark.metrics.device_idle_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
