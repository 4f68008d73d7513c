"""A header chain whose validator set moves, made from --seed and held
as wire bytes: what a light client's provider serves a client that was
away for some hours.

`n_vals` validators hold stake `stake_scale // (r + 2)` by rank r =
1..n_vals, rank dealt by a seeded shuffle (benchmark/configs/
net_100v.json's law). At seeded heights, none at height 1, the set
changes by ONE delta, applied through ValidatorSet.update_with_changes
as EndBlock's updates are: a stake change moves one seeded validator's
power by a seeded 1-5%, up or down (a delegation); a membership change
takes out the validator of least stake and lets a standby key in with
that stake plus one, so the set stays at its cap. Every member of the
signing set signs every commit, each precommit with a timestamp of its
own (height x 1e9 + index ns), so a header brings `n_vals` distinct
sign-bytes. The signatures are ops/ed25519.sign_batch's (the device on
a TPU).

The wire carries one signed header a height, and one validator-set
document a DISTINCT set: a client fetches `validators` only where a
header's `validators_hash` moved, so the FullCommits of a run of
unchanged headers share one set object, as `decode` builds them.
"""

from __future__ import annotations

import hashlib
import random
from typing import List

from benchmark.chain import LiteChain, _validator_seeds, chain_id_of
from benchmark.kvref import openssl_signer

STAKE, MEMBERSHIP = "stake", "membership"


class ChurnChain:
    def __init__(self, seed: int, n_headers: int, n_vals: int,
                 stake_changes: int, membership_changes: int,
                 stake_scale: int = 1_000_000, sign: str = "device"):
        from tendermint_tpu.lite.types import SignedHeader
        from tendermint_tpu.ops import ed25519
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.block import (BlockID, Commit, Header,
                                                PartSetHeader)
        from tendermint_tpu.types.validator_set import (Validator,
                                                        ValidatorSet)
        from tendermint_tpu.types.vote import (Vote, VoteType,
                                               sign_bytes_template)

        self.chain_id = chain_id_of("churn", seed)
        self.n_headers, self.n_vals = n_headers, n_vals
        rng = random.Random(f"{seed}/churn/sets")
        seeds = _validator_seeds(seed, n_vals + membership_changes, "churn")
        self.seed_of = {
            openssl_signer(s).public_key().public_bytes_raw(): s
            for s in seeds}
        pubs = list(self.seed_of)
        standby = pubs[n_vals:]
        ranks = list(range(1, n_vals + 1))
        rng.shuffle(ranks)
        valset = ValidatorSet([Validator(pk, stake_scale // (r + 2))
                               for pk, r in zip(pubs, ranks)])
        # which boundary moves what: heights 2..n_headers, distinct
        heights = rng.sample(range(2, n_headers + 1),
                             stake_changes + membership_changes)
        self.change_at = {h: MEMBERSHIP for h in heights[:membership_changes]}
        self.change_at.update(
            (h, STAKE) for h in heights[membership_changes:])

        # ---- the sets, height by height
        sets, self.set_of = [valset], []    # set_of[h - 1]: index in sets
        for h in range(1, n_headers + 1):
            kind = self.change_at.get(h)
            if kind == STAKE:
                v = rng.choice(valset.validators)
                step = max(1, v.voting_power * rng.randint(1, 5) // 100)
                if rng.random() < 0.5 and v.voting_power > step:
                    step = -step
                valset = valset.update_with_changes(
                    [Validator(v.pubkey, v.voting_power + step)])
            elif kind == MEMBERSHIP:
                out = min(valset.validators,
                          key=lambda v: (v.voting_power, v.address))
                valset = valset.update_with_changes(
                    [Validator(out.pubkey, 0),
                     Validator(standby.pop(0), out.voting_power + 1)])
            if kind is not None:
                sets.append(valset)
            self.set_of.append(len(sets) - 1)
        self.valsets_wire = [encoding.cdumps(vs.to_obj()) for vs in sets]

        # ---- headers, and what each validator signs
        parts = PartSetHeader(1, hashlib.sha256(b"churn-parts").digest())
        app = random.Random(f"{seed}/churn/app")
        headers, bids = [], []
        self.msgs: List[bytes] = []     # one a vote, in chain order
        self.signed_by: List[bytes] = []    # and the seed that signs it
        for h in range(1, n_headers + 1):
            vs = sets[self.set_of[h - 1]]
            header = Header(chain_id=self.chain_id, height=h, time_ns=h,
                            validators_hash=vs.hash(),
                            app_hash=app.randbytes(32))
            bid = BlockID(header.hash(), parts)
            headers.append(header)
            bids.append(bid)
            pre, suf = sign_bytes_template(self.chain_id, bid, h, 0,
                                           VoteType.PRECOMMIT)
            for j, val in enumerate(vs.validators):
                self.msgs.append(
                    (pre + str(h * 10 ** 9 + j) + suf).encode())
                self.signed_by.append(self.seed_of[val.pubkey])
        if sign == "device":
            self.sigs = ed25519.sign_batch(self.signed_by, self.msgs)
        else:       # a toy chain that must not compile the sign kernel
            signer = {s: openssl_signer(s).sign for s in seeds}
            self.sigs = [signer[s](m)
                         for s, m in zip(self.signed_by, self.msgs)]
        self.wire: List[bytes] = []
        lane = 0
        for i, h in enumerate(range(1, n_headers + 1)):
            precommits = []
            for j, val in enumerate(sets[self.set_of[i]].validators):
                v = Vote(val.address, j, h, 0, h * 10 ** 9 + j,
                         VoteType.PRECOMMIT, bids[i])
                v.signature = self.sigs[lane]
                lane += 1
                precommits.append(v)
            self.wire.append(encoding.cdumps(SignedHeader(
                headers[i], Commit(bids[i], precommits), bids[i]).to_obj()))
        self.n_sigs = lane

    def decode(self, wire: List[bytes] = None, valsets_wire=None,
               set_of=None):
        """(the set that signs height 1, [FullCommit]) fresh from the
        wire bytes, one ValidatorSet object a distinct set."""
        from tendermint_tpu.lite.types import FullCommit, SignedHeader
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.validator_set import ValidatorSet
        loads, from_obj = encoding.cloads, SignedHeader.from_obj
        sets = [ValidatorSet.from_obj(loads(raw)) for raw in
                (self.valsets_wire if valsets_wire is None else valsets_wire)]
        set_of = self.set_of if set_of is None else set_of
        return sets[0], [
            FullCommit(from_obj(loads(raw)), sets[set_of[i]])
            for i, raw in enumerate(self.wire if wire is None else wire)]

    # ------------------------------------------------- tampered chains
    # each returns decode()'s arguments, (wire, valsets_wire, set_of),
    # for the chain with `height` tampered, cut after `upto` (at the
    # tampered height unless the caller wants whole windows)

    def _upto(self, height: int, upto):
        upto = max(height, upto or 0)
        return (list(self.wire[:upto]), list(self.valsets_wire),
                list(self.set_of[:upto]))

    def flipped_signature(self, height: int, slot: int, upto=None):
        """One bit of one precommit's signature flipped."""
        from tendermint_tpu.types import encoding
        wire, sets, set_of = self._upto(height, upto)
        doc = encoding.cloads(wire[height - 1])
        vote = doc["commit"]["precommits"][slot]
        sig = bytes.fromhex(vote["signature"])
        vote["signature"] = (sig[:40] + bytes([sig[40] ^ 1])
                             + sig[41:]).hex()
        wire[height - 1] = encoding.cdumps(doc)
        return wire, sets, set_of

    def forged_header(self, height: int, upto=None):
        """A header nobody signed, dressed in the genuine commit's
        signatures: LiteChain's forgery, which reads the chain id and
        the height's wire bytes and nothing else of a chain."""
        wire, sets, set_of = self._upto(height, upto)
        wire[height - 1] = LiteChain.forged_header(self, height)
        return wire, sets, set_of

    def wrong_validators(self, height: int, upto=None):
        """The genuine header served with a validators document that
        does not hash to its `validators_hash`: one power off by one."""
        from tendermint_tpu.types import encoding
        wire, sets, set_of = self._upto(height, upto)
        doc = encoding.cloads(sets[set_of[height - 1]])
        doc["validators"][0]["voting_power"] += 1
        sets.append(encoding.cdumps(doc))
        set_of[height - 1] = len(sets) - 1
        return wire, sets, set_of

    def hostile_transition(self, height: int, seed: int, upto=None):
        """A set of fresh keys, as many as the cap, that signs a header
        of its own at `height` with its full quorum: nothing but the
        trusted set's endorsement can refuse it."""
        from tendermint_tpu.lite.types import SignedHeader
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.block import (BlockID, Commit, Header,
                                                PartSetHeader)
        from tendermint_tpu.types.validator_set import (Validator,
                                                        ValidatorSet)
        from tendermint_tpu.types.vote import Vote, VoteType
        wire, sets, set_of = self._upto(height, upto)
        signer = {}
        for s in _validator_seeds(seed, self.n_vals, "churn-hostile"):
            key = openssl_signer(s)
            signer[key.public_key().public_bytes_raw()] = key.sign
        valset = ValidatorSet([Validator(pk, 1000) for pk in signer])
        header = Header(chain_id=self.chain_id, height=height,
                        time_ns=height, validators_hash=valset.hash(),
                        app_hash=b"\xee" * 32)
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x66" * 32))
        votes = []
        for j, val in enumerate(valset.validators):
            v = Vote(val.address, j, height, 0, height * 10 ** 9 + j,
                     VoteType.PRECOMMIT, bid)
            v.signature = signer[val.pubkey](v.sign_bytes(self.chain_id))
            votes.append(v)
        wire[height - 1] = encoding.cdumps(
            SignedHeader(header, Commit(bid, votes), bid).to_obj())
        sets.append(encoding.cdumps(valset.to_obj()))
        set_of[height - 1] = len(sets) - 1
        return wire, sets, set_of
