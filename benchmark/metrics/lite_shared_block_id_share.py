"""Share of the block ids of the commits decoded from wire objects that
were not built but handed to a vote from the table of its commit:
`verifier_commit_block_ids_total`, how="shared" over shared + built, as
the counter stands when the run ends (it has no window: a pass's commits
are decoded between passes, and the warm pass's and `correct`'s are in
it). 64 votes for the commit's own block read 64 / 65. None where the
program has no such family (a parent commit) or the process verifier
sent nothing to a device (a CPU rehearsal), as `lite_columns_share`."""

from benchmark import program_spans
from benchmark.stats import share

LAYER = "verifier"
MOVES = "headers_per_s"

FAMILY = "verifier_commit_block_ids_total"


def read(r):
    from tendermint_tpu.models.verifier import default_verifier
    if program_spans.counter_total(FAMILY) is None \
            or not default_verifier().stats["jax_sigs"]:
        return None
    # counter_total reads a family without labels; this one has one
    from tendermint_tpu import telemetry
    shared, built = (telemetry.value(FAMILY, {"how": how}) or 0.0
                     for how in ("shared", "built"))
    return share(shared, shared + built)
