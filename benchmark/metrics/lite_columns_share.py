"""Share of the signatures dispatched to the device whose batch arrived
as columns and was prepared in place, not walked as triples:
`verifier_batch_sigs_total`, form="columns" over columns + items, as
the counter stands when the run ends (it has no window: the warm pass
is in it, and so are the batches of `correct` after the window, whose
tampered window is a list of triples). None where the program has no
such family (a parent commit) or the process verifier sent nothing to
a device (a CPU rehearsal), as `lite_h2d_bytes_per_sig`."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "verifier"
MOVES = "headers_per_s"

FAMILY = "verifier_batch_sigs_total"


def read(r):
    from tendermint_tpu.models.verifier import default_verifier
    if program_spans.counter_total(FAMILY) is None \
            or not default_verifier().stats["jax_sigs"]:
        return None
    # counter_total reads a family without labels; this one has one
    from tendermint_tpu import telemetry
    columns, items = (telemetry.value(FAMILY, {"form": form}) or 0.0
                      for form in ("columns", "items"))
    return share(columns, columns + items)
