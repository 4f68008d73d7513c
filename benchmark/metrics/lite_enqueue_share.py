"""Share of the passes' time handing chunks to the device
(`verify.enqueue`: the host-to-device transfers and the jitted call)."""

from benchmark import program_spans

LAYER = "device kernels"
MOVES = "headers_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.enqueue")
