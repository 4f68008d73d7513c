"""`lite_predecomp_share`'s reading in `chain_100v_churn.lite_follow`: the
share of the passes' time assembling predecompressed key rows
(`verify.predecomp`): with a key list that changes every 63 headers
nearly every chunk's key sequence is new to the memo. An entry of its
own because a test holds the `lite_` entry's `workloads` to the
constant-set cell alone."""

from benchmark.metrics.lite_predecomp_share import LAYER, read  # noqa: F401

MOVES = "headers_per_s"
