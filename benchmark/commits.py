"""Commits of one large validator set made from --seed: what a full node
is handed, one at a time, as the LastCommit of a proposed block.

`n_heights` consecutive heights of one constant set of `n_vals` equal
validators; every validator precommits every block, each with its own
clock (a timestamp of its own), so no two votes of the run share their
sign-bytes. The sign-bytes come from the plain reference
(`commitref.sign_bytes`), not from the program; the signatures from
ops/ed25519.sign_batch (the device on a TPU, as `chain.LiteChain`).
The commits and the set are held as wire bytes for the program, and as
fields (block ids, signatures; timestamps are computed) for the
reference. A seed changes contents and never sizes.
"""

from __future__ import annotations

import hashlib
import itertools
from typing import List, Optional, Tuple

from benchmark import commitref
from benchmark.chain import _validator_seeds, chain_id_of
from benchmark.commitref import NIL_BLOCK, PRECOMMIT, PlainVote
from benchmark.kvref import openssl_signer

SIGN_BLOCK = 4096       # validators signed in one call, all their heights


class CommitSet:
    def __init__(self, seed: int, n_vals: int, n_heights: int, power: int):
        from tendermint_tpu.ops import ed25519
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.validator_set import (Validator,
                                                        ValidatorSet)

        self.chain_id = chain_id_of("commit", seed)
        self.n_vals, self.n_heights, self.power = n_vals, n_heights, power
        seed_of = {}
        for s in _validator_seeds(seed, n_vals, "commit"):
            seed_of[openssl_signer(s).public_key().public_bytes_raw()] = s
        valset = ValidatorSet([Validator(pk, power) for pk in seed_of])
        self.pubkeys = [v.pubkey for v in valset.validators]
        self.addresses = [v.address for v in valset.validators]
        self.seeds = [seed_of[pk] for pk in self.pubkeys]
        self.valset_wire = encoding.cdumps(valset.to_obj())
        tag = f"{seed}/commit".encode()
        self.block_ids = [
            (hashlib.sha256(tag + b"/block/%d" % h).digest(), 1,
             hashlib.sha256(tag + b"/parts/%d" % h).digest())
            for h in self.heights()]
        self.msgs = [commitref.sign_bytes(self.chain_id, v)
                     for h in self.heights() for v in self.votes(h, signed=False)]
        # signed a block of validators at a time, every height of the
        # block in one call: a program whose cache of signing parameters
        # holds 4,096 seeds (PR 26's parent) then derives each public
        # key once, in pure Python, and not once a height (12 minutes)
        self.sigs: List[bytes] = [b""] * len(self.msgs)
        for lo in range(0, n_vals, SIGN_BLOCK):
            lanes = [(h - 1) * n_vals + idx for h in self.heights()
                     for idx in range(lo, min(lo + SIGN_BLOCK, n_vals))]
            signed = ed25519.sign_batch(
                [self.seeds[lane % n_vals] for lane in lanes],
                [self.msgs[lane] for lane in lanes])
            for lane, sig in zip(lanes, signed):
                self.sigs[lane] = sig
        self.wire = [self.to_wire(self.block_id(h), self.votes(h))
                     for h in self.heights()]

    def heights(self) -> range:
        return range(1, self.n_heights + 1)

    def block_id(self, height: int) -> commitref.BlockId:
        return self.block_ids[height - 1]

    def validators(self) -> List[Tuple[bytes, int]]:
        return [(pk, self.power) for pk in self.pubkeys]

    def items(self, height: int) -> List[Tuple[bytes, bytes, bytes]]:
        """The commit's (pubkey, sign-bytes, signature) triples."""
        lo = (height - 1) * self.n_vals
        return list(zip(self.pubkeys, self.msgs[lo:lo + self.n_vals],
                        self.sigs[lo:lo + self.n_vals]))

    def votes(self, height: int, signed: bool = True) -> List[PlainVote]:
        """The height's precommits as the reference takes them:
        validator `idx` stamps its vote `idx` ns after the height's
        base. Not `signed`: the signatures are left empty."""
        lo = (height - 1) * self.n_vals
        bid = self.block_id(height)
        return [PlainVote(height, 0, PRECOMMIT, height * 10 ** 9 + idx, bid,
                          self.sigs[lo + idx] if signed else b"")
                for idx in range(self.n_vals)]

    def nil_vote(self, height: int, idx: int) -> PlainVote:
        """Validator `idx`'s precommit for nil at `height`, signed on
        the host."""
        v = PlainVote(height, 0, PRECOMMIT, height * 10 ** 9 + idx,
                      NIL_BLOCK, b"")
        return v._replace(signature=openssl_signer(self.seeds[idx]).sign(
            commitref.sign_bytes(self.chain_id, v)))

    # ------------------------------------------------- the program's side

    def to_wire(self, block_id, votes) -> bytes:
        from tendermint_tpu.types import encoding
        return encoding.cdumps(
            program_commit(self.addresses, block_id, votes).to_obj())

    def decode(self):
        """(valset, [(block id, height, Commit)]) fresh from the wire
        bytes: what a node holds when a proposed block has arrived."""
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.block import Commit
        from tendermint_tpu.types.validator_set import ValidatorSet
        valset = ValidatorSet.from_obj(encoding.cloads(self.valset_wire))
        return valset, [
            (program_block_id(self.block_id(h)), h,
             Commit.from_obj(encoding.cloads(raw)))
            for h, raw in zip(self.heights(), self.wire)]


def program_block_id(block_id: commitref.BlockId):
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    block_hash, total, parts_hash = block_id
    return BlockID(block_hash, PartSetHeader(total, parts_hash))


def program_commit(addresses: List[bytes], block_id: commitref.BlockId,
                   votes: List[Optional[PlainVote]]):
    """The program's Commit of plain votes; slot i is the vote of the
    validator whose address is `addresses[i]`."""
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.vote import Vote
    bids = {}

    def bid_of(plain):
        if plain not in bids:
            bids[plain] = program_block_id(plain)
        return bids[plain]
    return Commit(bid_of(block_id), [
        None if v is None else Vote(
            addresses[i % len(addresses)], i, v.height, v.round,
            v.timestamp_ns, v.type, bid_of(v.block_id), v.signature)
        for i, v in enumerate(votes)])


def flip_bit(sig: bytes, at: int) -> bytes:
    return sig[:at] + bytes([sig[at] ^ 1]) + sig[at + 1:]


def commit_cases(cs: CommitSet, chunk: int, rng) -> list:
    """[(name, block id, height, votes)]: whole commits, each of a height
    of its own, that a verifier of one commit has to tell apart. `chunk`
    is where the verifier cuts a commit: lanes from there on are the
    tail chunk's."""
    n = cs.n_vals
    height = itertools.cycle(
        rng.sample(list(cs.heights()), min(7, cs.n_heights)))

    def flipped(lane: int):
        h = next(height)
        votes = cs.votes(h)
        votes[lane] = votes[lane]._replace(
            signature=flip_bit(votes[lane].signature, rng.randrange(64)))
        return h, votes

    def split(n_for: int):
        """`n_for` validators sign the block and the others sign nil,
        the two kinds spread over the whole commit."""
        h = next(height)
        votes = cs.votes(h)
        for idx in rng.sample(range(n), n - n_for):
            votes[idx] = cs.nil_vote(h, idx)
        return h, votes

    def absent(n_absent: int):
        h = next(height)
        votes: List[Optional[PlainVote]] = list(cs.votes(h))
        for idx in rng.sample(range(n), n_absent):
            votes[idx] = None
        return h, votes

    def short():
        h = next(height)
        return h, cs.votes(h)[:-1]

    def other_height():
        h = next(height)
        votes = cs.votes(h)
        lane = rng.randrange(1, n)
        votes[lane] = votes[lane]._replace(height=h + 1)
        return h, votes

    two_thirds = 2 * n // 3     # the most validators that are no quorum
    made = [("bad_signature_in_first_chunk",
             flipped(rng.randrange(min(chunk, n)))),
            ("bad_signature_in_tail_chunk",
             flipped(rng.randrange(min(chunk, n - 1), n))),
            ("two_thirds_and_no_more", split(two_thirds)),
            ("two_thirds_and_one", split(two_thirds + 1)),
            ("three_tenths_absent", absent(3 * n // 10)),
            ("one_vote_short", short()),
            ("vote_of_another_height", other_height())]
    return [(name, cs.block_id(h), h, votes) for name, (h, votes) in made]
