"""Share of the passes' time in `update_state` (`apply.update`, one
event a block: EndBlock's validator updates through
`update_with_changes`, the proposer rotation over every power, the next
State)."""

from benchmark import program_spans

LAYER = "apply and Merkle"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "apply.update")
