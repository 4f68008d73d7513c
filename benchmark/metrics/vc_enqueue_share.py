"""Share of the passes' time handing a commit's two chunks to the device
(`verify.enqueue`: transfers and the jitted call, per chunk)."""

from benchmark import program_spans

LAYER = "device kernels"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "verify.enqueue")
