"""The plain reference for a light client that follows a chain whose
validator set moves: one header at a time, one OpenSSL verify a
signature. Nothing here imports `tendermint_tpu`; what it reads is the
wire's own JSON, parsed with `json`.

    validators   [(pubkey32, voting_power)], ascending by address
    full commit  PlainFullCommit: the header's wire object, the block id
                 it was committed under, the commit's votes with the
                 address each one claims, and the set that signed it

Upstream (Tendermint v0.16 `lite/`): a `FullCommit` is a signed header
and the validator set that signed it (`lite/commit.go`);
`FullCommit.ValidateBasic` holds the header's chain id, its
`ValidatorsHash` against the set's hash and the commit's block id
against the header's hash; `StaticCertifier.Certify`
(`lite/static_certifier.go:57`) then runs `VerifyCommit` under the
trusted set, which is `commitref.verify_commit` here. A header whose
set is not the trusted one goes through `DynamicCertifier.Update`
(`lite/dynamic_certifier.go:70`), which is `VerifyCommitAny`.

Departures from upstream, each on purpose:
- **the transition rule** is this system's adjacent-height rule and not
  v0.16's `VerifyCommitAny`: (1) the header's own set certifies it, every
  signer counted, by plain `VerifyCommit`; (2) among the commit's votes
  for the block, those whose address the TRUSTED set knows, each
  validator once, must verify under the trusted set's key for that
  address and carry STRICTLY more than 1/3 of the trusted set's stake
  (later Tendermint's trust level). `VerifyCommitAny` counts only the
  overlap toward the new set's 2/3 and so refuses honest commits once a
  validator joins or leaves; the program keeps it for jumps;
- heights are consecutive: a height that is not the next one is refused,
  where upstream's inquiring certifier would bisect;
- a hash of a validator set is this system's: the Merkle root over one
  leaf a validator, in the set's order, the leaf the canonical JSON
  `{"pubkey":"<hex>","voting_power":<int>}` (sorted keys, minimal
  separators: `types/validator_set.py` `hash`), where upstream hashes
  go-wire's encoding of (address, pubkey, power, accum). The tree is
  `ops/merkle.py`'s: leaf sha256(0x00|item), inner sha256(0x01|l|r),
  leaves padded with zero digests to a power of two, sealed with
  sha256(0x02|count as 8 bytes little-endian|root);
- a header's hash is that tree over one leaf a field of the wire's
  header object, `{"<field>":<value>}` in ascending field order
  (`types/block.py` `Header.hash`), where upstream's is a merkle map;
- an address is sha256(pubkey)[:20], this system's, and a set's order is
  ascending address;
- signatures and stake as `commitref` has them: OpenSSL one at a time,
  Python ints, `3 * tally > 2 * total`; the 1/3 is `3 * tally > total`.
  An endorsing vote whose trusted key is the key of its slot in the
  signing set is the very triple `verify_commit` accepted a moment
  before, and is not sent to OpenSSL a second time;
- the answer is an `Outcome`, where upstream returns an error. Of two
  faults in one commit the one found first is upstream's order's (vote
  by vote: structure, then its signature), which for a commit with
  several faults of different kinds need not be the program's (all
  structure, then all signatures).
"""

from __future__ import annotations

import hashlib
import json
from typing import Callable, Iterable, List, NamedTuple, Optional, Tuple

from benchmark import commitref, stakeref
from benchmark.commitref import BlockId, PlainVote
from benchmark.kvref import merkle_root_of_digests, openssl_verify

Validators = List[Tuple[bytes, int]]        # (pubkey, power), by address

# why a header is refused
HEIGHT = "height"                           # not the next height
CHAIN_ID = "chain_id"
VALIDATORS_HASH = "validators_hash"         # the set is not the header's
HEADER_HASH = "header_hash"                 # the commit is for another
COMMIT = "commit"                           # size, height, round, type
SIGNATURE = "signature"
QUORUM = "quorum"                           # not +2/3 of the signing set
ENDORSEMENT_SIGNATURE = "endorsement_signature"
ENDORSEMENT = "endorsement"                 # not >1/3 of the trusted set


class PlainFullCommit(NamedTuple):
    header: dict                    # the wire's object, values untouched
    block_id: BlockId
    commit: List[Optional[PlainVote]]
    addresses: List[Optional[bytes]]        # each vote's claimed address
    validators: Validators


class Outcome(NamedTuple):
    height: int                     # the last height certified
    trusted: Validators             # the set trusted there
    changes: int                    # changes of set crossed
    refused_at: Optional[int] = None
    kind: Optional[str] = None
    why: str = ""


def address_of(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


def _canonical(obj) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      ensure_ascii=False).encode()


def _root(items: Iterable[bytes]) -> bytes:
    return merkle_root_of_digests(
        [hashlib.sha256(b"\x00" + it).digest() for it in items])


def validators_hash(validators: Validators) -> bytes:
    return _root(_canonical({"pubkey": pub.hex(), "voting_power": power})
                 for pub, power in validators)


def header_hash(header: dict) -> bytes:
    return _root(_canonical({k: header[k]}) for k in sorted(header))


def parse_validators(wire: bytes) -> Validators:
    """A validator-set document as a light client's provider serves it,
    put in the set's order. Accumulators and the proposer are not part
    of what a light client checks."""
    vals = [(bytes.fromhex(v["pubkey"]), int(v["voting_power"]))
            for v in json.loads(wire)["validators"]]
    vals.sort(key=lambda v: address_of(v[0]))
    return vals


def _block_id(obj: dict) -> BlockId:
    return (bytes.fromhex(obj["hash"]), int(obj["parts"]["total"]),
            bytes.fromhex(obj["parts"]["hash"]))


def parse_full_commit(signed_header_wire: bytes,
                      validators: Validators) -> PlainFullCommit:
    doc = json.loads(signed_header_wire)
    commit, addresses = [], []
    for v in doc["commit"]["precommits"]:
        if v is None:
            commit.append(None)
            addresses.append(None)
            continue
        commit.append(PlainVote(
            int(v["height"]), int(v["round"]), int(v["type"]),
            int(v["timestamp_ns"]), _block_id(v["block_id"]),
            bytes.fromhex(v["signature"])))
        addresses.append(bytes.fromhex(v["validator_address"]))
    return PlainFullCommit(doc["header"], _block_id(doc["block_id"]),
                           commit, addresses, validators)


def endorsement(chain_id: str, trusted: Validators, fc: PlainFullCommit,
                check_signatures: bool = True) -> Optional[Tuple[str, str]]:
    """The trusted set's side of a change of set, judged after
    `verify_commit` has accepted the commit under `fc.validators`: None
    if the trusted set endorses it, else (kind, why)."""
    key_of = {address_of(pub): pub for pub, _power in trusted}
    signers = []
    for slot, (vote, addr) in enumerate(zip(fc.commit, fc.addresses)):
        if vote is None or vote.block_id != fc.block_id:
            continue
        if addr not in key_of or addr in signers:
            continue        # unknown to the trusted set, or counted
        signers.append(addr)
        if check_signatures and key_of[addr] != fc.validators[slot][0] \
                and not openssl_verify(
                    key_of[addr], commitref.sign_bytes(chain_id, vote),
                    vote.signature):
            return ENDORSEMENT_SIGNATURE, f"invalid signature of {addr.hex()}"
    got, total, _two_thirds = stakeref.tally(
        [(address_of(pub), pub, power) for pub, power in trusted], signers)
    if 3 * got > total:
        return None
    return ENDORSEMENT, f"trusted stake behind the block: {got} of {total}"


def commit_unverified(fc: PlainFullCommit) -> Optional[str]:
    """`commitref.verify_commit` less its signature checks, for a height
    whose signatures the caller leaves to the other heights: sizes,
    heights, rounds, types and the tally."""
    if len(fc.validators) != len(fc.commit):
        return f"wrong set size: {len(fc.validators)} vs {len(fc.commit)}"
    votes = [v for v in fc.commit if v is not None]
    height = fc.header["height"]
    if not votes or any(v.height != height for v in votes):
        return f"wrong height: {height}"
    if any(v.round != votes[0].round for v in votes):
        return f"wrong round: {votes[0].round}"
    if any(v.type != commitref.PRECOMMIT for v in votes):
        return "not precommit"
    vals = [(address_of(pub), pub, power) for pub, power in fc.validators]
    got, total, two_thirds = stakeref.tally(vals, [
        val[0] for val, vote in zip(vals, fc.commit)
        if vote is not None and vote.block_id == fc.block_id])
    if two_thirds:
        return None
    return f"insufficient voting power: got {got} of {total}"


def _commit_kind(why: str) -> str:
    if why.startswith("invalid signature"):
        return SIGNATURE
    if why.startswith("insufficient voting power"):
        return QUORUM
    return COMMIT


def follow(chain_id: str, trusted: Validators,
           full_commits: Iterable[PlainFullCommit], next_height: int = 1,
           check_signatures: Callable[[int], bool] = lambda height: True
           ) -> Outcome:
    """Walk `full_commits` from `next_height` under `trusted`, one
    header at a time. A height for which `check_signatures(height)` is
    False gets every check but OpenSSL's: a caller that cannot afford
    OpenSSL on a whole chain says which heights get it."""
    height, changes = next_height - 1, 0
    trusted_hash = validators_hash(trusted)

    def refused(kind: str, why: str) -> Outcome:
        return Outcome(height, trusted, changes, height + 1, kind, why)

    hashed = vhash = None       # the set hashed last: a run of headers
    for fc in full_commits:     # hands over one list object
        h = fc.header
        if h["height"] != height + 1:
            return refused(HEIGHT, f"got {h['height']}")
        if h["chain_id"] != chain_id:
            return refused(CHAIN_ID, repr(h["chain_id"]))
        if fc.validators is not hashed:
            hashed, vhash = fc.validators, validators_hash(fc.validators)
        if bytes.fromhex(h["validators_hash"]) != vhash:
            return refused(VALIDATORS_HASH, "the set is not the header's")
        if fc.block_id[0] != header_hash(h):
            return refused(HEADER_HASH, "the commit is for another header")
        # the header's own set certifies it: the trusted set where
        # nothing moved, the set it carries where it did
        sigs = check_signatures(height + 1)
        why = commitref.verify_commit(
            chain_id, fc.validators, fc.block_id, h["height"], fc.commit) \
            if sigs else commit_unverified(fc)
        if why is not None:
            return refused(_commit_kind(why), why)
        if vhash != trusted_hash:
            bad = endorsement(chain_id, trusted, fc, sigs)
            if bad is not None:
                return refused(*bad)
            trusted, trusted_hash, changes = fc.validators, vhash, changes + 1
        height += 1
    return Outcome(height, trusted, changes)
