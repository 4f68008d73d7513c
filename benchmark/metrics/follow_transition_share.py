"""Share of the passes' time a follower spends judging the changes of
validator set in a window and moving its trust (`lite.transition`, the
last part of `lite.check`, one event a window: the trusted set's
endorsement tally of every boundary, then ContinuousCertifier._trust).
Nothing to read where no change of set was ever crossed
(`lite_transitions_total`, membership and stake, stands at 0)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "headers_per_s"

FAMILY = "lite_transitions_total"


def read(r):
    if program_spans.counter_total(FAMILY) is None:
        return None
    from tendermint_tpu import telemetry
    if not sum(telemetry.value(FAMILY, {"kind": kind}) or 0.0
               for kind in ("stake", "membership")):
        return None
    return program_spans.share_of_passes(r, "lite.transition")
