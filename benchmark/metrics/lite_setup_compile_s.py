"""`setup_compile_s` in the lite cell: an entry of its own because that
cell's end-to-end metric is `headers_per_s`."""

from benchmark.metrics.setup_compile_s import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
