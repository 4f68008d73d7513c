"""Share of the profiled part in which no operation ran on the device."""

from benchmark import readers

LAYER = "device"
MOVES = "commits_per_s"


def read(r):
    return readers.device_idle_share(r)
