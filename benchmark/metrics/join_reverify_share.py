"""Share of the passes' time inside the window engine's synchronous
`verify_commit` of a block whose pooled verdicts were thrown away
(`sync.reverify`, one event a block re-verified: collect, the verify
itself, which under `auto_threshold` signatures is scalar on the host,
and the tally)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "sync.reverify")
