"""The plain reference for a full node that joins a chain by fast-sync
while the chain's validator set moves: one block at a time, from the
wire's own JSON. Nothing here imports `tendermint_tpu`.

    genesis     the genesis document's wire bytes: chain id, validators
    blocks      each block's wire bytes, in order; the last one only
                lends its LastCommit (the commit FOR the block below it)

Upstream (Tendermint v0.16): `blockchain/reactor.go` poolRoutine takes
the two lowest blocks it holds, judges the first by the second's
LastCommit under the validators of its state (`VerifyCommit`), saves it
and applies it; `state/execution.go:286-338` updateState then makes the
next height's set from EndBlock's validator updates. So the set that
judges a block's commit is the set IN FORCE AT ITS HEIGHT, which a node
knows only by having executed every block below. Written out here:

for block b at height h, with `vals` the set in force at h:
- its header names h, the chain, and `vals` (its `validators_hash` is
  `literef.validators_hash(vals)`), and its `app_hash` is the
  application's after block h - 1 (`kvref.PlainKV`'s);
- the block above it carries, as its LastCommit, a commit for b's id
  (the id that block's header gives as `last_block_id`, whose hash is
  `literef.header_hash` of b's header), which `commitref.verify_commit`
  accepts under `vals`;
- then b is applied: its key=value transactions to the store, and its
  `val:<pubkey hex>/<power>` transactions, all of the block as one
  batch in block order, to the set: a power of 0 removes, any other
  power adds the key or replaces its power (`update` has the cases the
  app refuses); the result, ascending by address, is in force from
  h + 1. A `val:` transaction is no key of the store (the KVStore
  app keeps it out, as abci's persistent kvstore example does).

Departures from upstream are `commitref`'s and `literef`'s (sign-bytes,
hashes, addresses and the Merkle tree are this system's; OpenSSL checks
one signature at a time; stake is a Python int). Of a block's other
header fields (data hash, results hash, consensus hash, total_txs)
nothing is held here: the program checks them, and a chain made by the
program's own executor has them right.
"""

from __future__ import annotations

import json
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

from benchmark import commitref, literef
from benchmark.commitref import PlainVote
from benchmark.kvref import PlainKV
from benchmark.literef import (Validators, _block_id, _commit_kind,
                               address_of)

# why a block is refused
HEIGHT, CHAIN_ID = literef.HEIGHT, literef.CHAIN_ID
VALIDATORS_HASH = literef.VALIDATORS_HASH   # the header names another set
APP_HASH = "app_hash"                       # not the replay's
BLOCK_ID = "block_id"                       # the commit is for another block
COMMIT, SIGNATURE, QUORUM = literef.COMMIT, literef.SIGNATURE, literef.QUORUM


class Outcome(NamedTuple):
    height: int                         # the last height applied
    validators: Validators              # the set in force at height + 1
    app_hashes: List[bytes]             # after block 1, 2, ... height
    validators_hashes: List[bytes]      # of the set in force at 1 ... height + 1
    refused_at: Optional[int] = None
    kind: Optional[str] = None
    why: str = ""


def parse_genesis(wire: bytes) -> Tuple[str, Validators]:
    """(chain id, the set in force at height 1)."""
    doc = json.loads(wire)
    return doc["chain_id"], in_order(
        (bytes.fromhex(v["pubkey"]), int(v["power"]))
        for v in doc["validators"])


def in_order(validators) -> Validators:
    """A set as the program orders it: ascending by address."""
    return sorted(validators, key=lambda v: address_of(v[0]))


def txs_of(block: dict) -> List[bytes]:
    return [bytes.fromhex(t) for t in block["data"]["txs"]]


def update(validators: Validators, txs: Sequence[bytes]) -> Validators:
    """The set after one block: its `val:` transactions applied in
    block order, each as the KVStore app takes it at DeliverTx. One the
    app refuses there changes nothing and the block goes on: a
    transaction it cannot parse (a key that is not 32 bytes of hex, a
    power that is no whole number or is negative), the removal of a key
    the set does not hold, and the removal of the set's last member."""
    power_of = dict(validators)
    for tx in txs:
        if not tx.startswith(b"val:"):
            continue
        pub, _, power = tx[4:].partition(b"/")
        try:
            pub, power = bytes.fromhex(pub.decode()), int(power)
        except (ValueError, UnicodeDecodeError):
            continue
        if len(pub) != 32 or power < 0:
            continue
        if power == 0:
            if pub in power_of and len(power_of) > 1:
                del power_of[pub]
        else:
            power_of[pub] = power
    return in_order(power_of.items())


def commit_of(block_above: dict, header: dict,
              validators: Validators) -> literef.PlainFullCommit:
    """The commit for the block of `header` that the block above it
    carries, with the id it was committed under and the set to judge it
    by, in `literef`'s form."""
    votes, addresses = [], []
    for v in block_above["last_commit"]["precommits"]:
        if v is None:
            votes.append(None)
            addresses.append(None)
            continue
        votes.append(PlainVote(
            int(v["height"]), int(v["round"]), int(v["type"]),
            int(v["timestamp_ns"]), _block_id(v["block_id"]),
            bytes.fromhex(v["signature"])))
        addresses.append(bytes.fromhex(v["validator_address"]))
    return literef.PlainFullCommit(
        header, _block_id(block_above["header"]["last_block_id"]),
        votes, addresses, validators)


def replay(genesis_wire: bytes, blocks_wire: Sequence[bytes],
           check_signatures: Callable[[int], bool] = lambda height: True
           ) -> Outcome:
    """Walk `blocks_wire` from height 1. A height for which
    `check_signatures(height)` is False gets every check but OpenSSL's
    on its commit (sizes, heights, rounds, types and the tally under
    the set in force): a caller that cannot afford OpenSSL on a whole
    chain says which heights get it."""
    chain_id, vals = parse_genesis(genesis_wire)
    app = PlainKV()
    app_hash = b""                  # what block 1's header carries
    app_hashes: List[bytes] = []
    vals_hashes = [literef.validators_hash(vals)]

    def refused(kind: str, why: str) -> Outcome:
        return Outcome(len(app_hashes), vals, app_hashes, vals_hashes,
                       len(app_hashes) + 1, kind, why)

    above = None                    # one pair of blocks parsed at a time
    for height, raw in enumerate(blocks_wire):
        block, above = above, json.loads(raw)
        if block is None:
            continue
        h = block["header"]
        if h["height"] != height:
            return refused(HEIGHT, f"got {h['height']}")
        if h["chain_id"] != chain_id:
            return refused(CHAIN_ID, repr(h["chain_id"]))
        if bytes.fromhex(h["validators_hash"]) != vals_hashes[-1]:
            return refused(VALIDATORS_HASH,
                           "the header names another set than the one the "
                           "blocks below make")
        if bytes.fromhex(h["app_hash"]) != app_hash:
            return refused(APP_HASH, "the header's app hash is not the "
                                     "replay's")
        fc = commit_of(above, h, vals)
        if fc.block_id[0] != literef.header_hash(h):
            return refused(BLOCK_ID, "the commit above is for another block")
        why = commitref.verify_commit(
            chain_id, vals, fc.block_id, height, fc.commit) \
            if check_signatures(height) else literef.commit_unverified(fc)
        if why is not None:
            return refused(_commit_kind(why), why)
        txs = txs_of(block)
        vals = update(vals, txs)
        app_hash = app.apply_block(
            [tx for tx in txs if not tx.startswith(b"val:")])
        app_hashes.append(app_hash)
        vals_hashes.append(literef.validators_hash(vals))
    return Outcome(len(app_hashes), vals, app_hashes, vals_hashes)
