"""Share of the profiled part in which a Pallas kernel of the program ran
on the device, by the names the trace gives them."""

from benchmark import readers

LAYER = "device kernels"
MOVES = "commits_per_s"


def read(r):
    return readers.kernel_busy_share(r)
