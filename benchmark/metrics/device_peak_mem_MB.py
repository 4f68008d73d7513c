"""Peak bytes in use on the fullest device, in MB (memory_stats)."""

LAYER = "device"
MOVES = "commits_per_s"


def read(r):
    return None if r.memory_peak_bytes is None else r.memory_peak_bytes / 1e6
