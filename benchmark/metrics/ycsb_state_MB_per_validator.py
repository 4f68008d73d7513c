"""The process' resident set after the four validators loaded their
stores less before, over four, in megabytes: records, keys, hashes and
the tree's nodes: what the load of `setup_s` builds."""

LAYER = "state tree and read path"
MOVES = "setup_s"


def read(r):
    got = r.client.get("load_rss_bytes_per_validator")
    return None if got is None else got / 1e6
