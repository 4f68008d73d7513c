"""Share of the profiled parts (some seconds of the window, and the
audit) in which no operation ran on the device."""

from benchmark import readers

LAYER = "device"
MOVES = "commit_p50_ms"


def read(r):
    return readers.device_idle_share(r)
