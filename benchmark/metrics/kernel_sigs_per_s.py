"""Signatures the verifier sent to the device during the profiled part
over the seconds its Pallas kernels ran there."""

from benchmark import readers

LAYER = "device kernels"
MOVES = "commits_per_s"


def read(r):
    return readers.kernel_sigs_per_s(r)
