"""Share of the passes' time inside the window engine's live judge
(`sync.judge`, one event a block that came with lanes:
`ValidatorSet.check_commit_lanes` under the set in force: the lanes'
keys compared with the live keys, the lanes under another key, the
joiners the window's set had never seen, verified again scalar on the
host in one call a block, and the live stake tallied)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.judge")
