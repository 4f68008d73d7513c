"""Signatures the verifier was handed in the window (`BatchVerifier.stats`
"sigs", device and host alike) over the signatures the blocks applied
carry, one a validator a block: 1 where every commit is verified once,
2 where every block's lanes are verified on the device, thrown away and
verified again on the host."""

LAYER = "verifier"
MOVES = "commits_per_s"


def read(r):
    needed = r.counters.get("join.needed_sigs")
    if not needed:
        return None
    return r.counters["verifier.sigs"] / needed
