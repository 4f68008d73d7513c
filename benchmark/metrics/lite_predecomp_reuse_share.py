"""Share of the chunks that got predecompressed rows whose arrays were
reused from the memo of whole key sequences, not built row by row:
`verifier_predecomp_assembled_total`, how="reused" over reused + built,
as the counter stands when the run ends (it has no window; every verify
dispatch of a traced run is in it, the warm pass too)."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "verifier"
MOVES = "headers_per_s"

FAMILY = "verifier_predecomp_assembled_total"


def read(r):
    if program_spans.counter_total(FAMILY) is None:
        return None
    # counter_total reads a family without labels; this one has one
    from tendermint_tpu import telemetry
    reused, built = (telemetry.value(FAMILY, {"how": how}) or 0.0
                     for how in ("reused", "built"))
    return share(reused, reused + built)
