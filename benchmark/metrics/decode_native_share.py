"""Share of the blocks decoded from wire bytes whose transaction list
the native decoder filled from the wire's hex
(`native/codec.cpp` `split_hex_array`) and not `json.loads` and
`bytes.fromhex`: `wire_block_decodes_total`, how="native" over native +
pure, as the counter stands when the run ends (it has no window: the
warm pass's blocks and those `correct` decodes are in it). 100 unless
the extension is not built or a block made the decoder raise Fallback.
None where the program has no such family (a parent commit) or has
decoded no block."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "sync window engine"
MOVES = "commits_per_s"

FAMILY = "wire_block_decodes_total"


def read(r):
    if program_spans.counter_total(FAMILY) is None:
        return None
    # counter_total reads a family without labels; this one has one
    from tendermint_tpu import telemetry
    native, pure = (telemetry.value(FAMILY, {"how": how}) or 0.0
                    for how in ("native", "pure"))
    return share(native, native + pure)
