"""The plain reference for one commit: Tendermint v0.16 `VerifyCommit`
(types/validator_set.go:229-273) written out over plain data. Nothing
here imports `tendermint_tpu`.

    validators  [(pubkey32, voting_power)] in the set's order
    commit      [PlainVote or None], one slot per validator
    block_id    (hash, parts_total, parts_hash)

Upstream, step by step: the commit has one slot per validator; its
height (that of its first vote) is the height asked for; then for each
vote in order, skipping empty slots: the height, the round (that of the
first vote), the type (precommit), ONE `VerifyBytes` of the vote's
canonical sign-bytes under the validator at that index; a valid vote
for another block (or for nil) is no error and counts for nothing; a
vote for the block adds its validator's stake. Accepted iff the tally
is more than two thirds of the total stake.

Departures from upstream, each on purpose:
- sign-bytes are this system's canonical JSON of a vote (sorted keys,
  minimal separators, bytes as lowercase hex, time as integer
  nanoseconds; no validator identity in them), built here with `json`
  alone, where upstream signs go-wire's canonical JSON;
- a signature is checked by OpenSSL (RFC 8032, through
  `kvref.openssl_verify`), one at a time, where upstream calls
  go-crypto's ed25519;
- stake is a Python int: upstream's `total*2/3` in int64 floors, and
  `tally > floor(2*total/3)` is `3*tally > 2*total` for whole numbers,
  which is what is written here;
- the answer is None (accepted) or the reason refused, where upstream
  returns an error.
"""

from __future__ import annotations

import json
from typing import List, NamedTuple, Optional, Sequence, Tuple

from benchmark.kvref import openssl_verify

PRECOMMIT = 2
BlockId = Tuple[bytes, int, bytes]      # hash, parts total, parts hash
NIL_BLOCK: BlockId = (b"", 0, b"")


class PlainVote(NamedTuple):
    height: int
    round: int
    type: int
    timestamp_ns: int
    block_id: BlockId
    signature: bytes


def sign_bytes(chain_id: str, vote: PlainVote) -> bytes:
    """What a validator signs: the vote without its signature and
    without who cast it."""
    block_hash, parts_total, parts_hash = vote.block_id
    return json.dumps({
        "@chain_id": chain_id,
        "@type": "vote",
        "block_id": {"hash": block_hash.hex(),
                     "parts": {"hash": parts_hash.hex(),
                               "total": parts_total}},
        "height": vote.height,
        "round": vote.round,
        "timestamp_ns": vote.timestamp_ns,
        "type": vote.type,
    }, sort_keys=True, separators=(",", ":"), ensure_ascii=False).encode()


def verify_commit(chain_id: str, validators: Sequence[Tuple[bytes, int]],
                  block_id: BlockId, height: int,
                  commit: List[Optional[PlainVote]]) -> Optional[str]:
    """None if the commit is accepted, else why it is refused."""
    if len(validators) != len(commit):
        return f"wrong set size: {len(validators)} vs {len(commit)}"
    first = next((v for v in commit if v is not None), None)
    commit_height = first.height if first else 0
    commit_round = first.round if first else 0
    if height != commit_height:
        return f"wrong height: {height} vs {commit_height}"
    tallied = 0
    for idx, vote in enumerate(commit):
        if vote is None:
            continue
        if vote.height != height:
            return f"wrong height: {height} vs {vote.height} @ index {idx}"
        if vote.round != commit_round:
            return f"wrong round: {commit_round} vs {vote.round} @ index {idx}"
        if vote.type != PRECOMMIT:
            return f"not precommit @ index {idx}"
        pubkey, power = validators[idx]
        if not openssl_verify(pubkey, sign_bytes(chain_id, vote),
                              vote.signature):
            return f"invalid signature @ index {idx}"
        if vote.block_id != block_id:
            continue
        tallied += power
    total = sum(power for _pubkey, power in validators)
    if 3 * tallied > 2 * total:
        return None
    return f"insufficient voting power: got {tallied} of {total}"
