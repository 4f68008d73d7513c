"""Share of the passes' time inside the window engine's repairs
(`sync.repair`, one event a block applied that brought a key into force
under an address the set before did not hold: the newcomers' lanes in
every block collected and not yet applied, verified under that key in
one call on the verifier, device or host by its own routing, and laid
over the windows' keys and verdicts). What leaves `grow_judge_share`
for the repairs shows here."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.repair")
