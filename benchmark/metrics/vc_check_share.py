"""Share of the passes' time spent judging a commit's verdicts and
tallying its stake (`commit.check`: `check_commit_results`)."""

from benchmark import program_spans

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    return program_spans.share_of_passes(r, "commit.check")
