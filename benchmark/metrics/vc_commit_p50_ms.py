"""Median milliseconds of one `ValidatorSet.verify_commit` call, over
every call of the whole passes (`Pass.extra["call_s"]`): what a
validator waits before it may prevote."""

from benchmark.stats import percentile

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    calls = [s for p in r.passes for s in p.extra.get("call_s", ())]
    return 1000.0 * percentile(calls, 0.5) if calls else None
