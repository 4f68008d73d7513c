"""Share of the passes' time with nothing of the verifier's queued on
the device, seen from the host: 100 minus the union of
`verify.inflight` (from the end of a dispatch's first enqueue to the
end of its fetch)."""

from benchmark import program_spans

LAYER = "device"
MOVES = "headers_per_s"


def read(r):
    return program_spans.uncovered_share_of_passes(r, "verify.inflight")
