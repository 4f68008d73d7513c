"""Vote — a signed prevote/precommit (capability parity: types/vote.go)."""

from __future__ import annotations

import functools
import json
from dataclasses import dataclass, field
from typing import Optional

from tendermint_tpu.types import encoding
from tendermint_tpu.types.keys import address_of
from tendermint_tpu.utils import clock


class VoteType:
    PREVOTE = 1
    PRECOMMIT = 2

    @staticmethod
    def valid(t: int) -> bool:
        return t in (VoteType.PREVOTE, VoteType.PRECOMMIT)


def now_ns() -> int:
    return clock.now_ns()


@functools.lru_cache(maxsize=64)
def _json_string(s: str) -> str:
    # a chain id, encoded once: a window of commits asks per block id
    return json.dumps(s, ensure_ascii=False)


def sign_bytes_template(chain_id: str, block_id, height: int, round_: int,
                        type_: int) -> tuple:
    """(prefix, suffix) strings around the timestamp of the canonical
    vote sign bytes — THE single definition of the vote sign-byte
    layout. Vote.sign_bytes fills one timestamp; batch verifiers
    (ValidatorSet.commit_verification_items) reuse one template for a
    whole commit, whose votes differ only in timestamp per block_id."""
    cid = _json_string(chain_id)
    return (
        f'{{"@chain_id":{cid},"@type":"vote",'
        f'"block_id":{{"hash":"{block_id.hash.hex()}",'
        f'"parts":{{"hash":"{block_id.parts.hash.hex()}",'
        f'"total":{block_id.parts.total}}}}},'
        f'"height":{height},"round":{round_},'
        f'"timestamp_ns":',
        f',"type":{type_}}}')


@dataclass
class Vote:
    validator_address: bytes
    validator_index: int
    height: int
    round: int
    timestamp_ns: int
    type: int
    block_id: "BlockID"          # zero BlockID = nil-vote
    signature: bytes = b""

    def sign_obj(self, chain_id: str):
        """Deterministic sign-bytes content (replaces canonical_json.go:58).
        Excludes validator identity — a vote's meaning is (chain, h, r,
        type, block, time); identity is bound by the key itself."""
        return {
            "@chain_id": chain_id,
            "@type": "vote",
            "height": self.height,
            "round": self.round,
            "timestamp_ns": self.timestamp_ns,
            "type": self.type,
            "block_id": self.block_id.to_obj(),
        }

    def sign_bytes(self, chain_id: str) -> bytes:
        """Canonical encoding of sign_obj, emitted directly: this is the
        single hottest encode in the framework (one per vote ingested,
        per commit signature verified, per fast-sync/lite signature
        prepared), and the generic dict walk costs ~20us vs ~2us here.
        Byte-identical to encoding.cdumps(self.sign_obj(chain_id)) —
        pinned by test_types.test_vote_sign_bytes_fast_path."""
        pre, suf = sign_bytes_template(chain_id, self.block_id,
                                       self.height, self.round, self.type)
        return (pre + str(self.timestamp_ns) + suf).encode()

    def to_obj(self):
        # cached per signature value: a commit re-encodes its V votes
        # for the block bytes, the stored commit AND the commit hash —
        # at V=256 the rebuild cost dominated the fast-sync hot loop.
        # Safe because a vote's fields never change after signing (the
        # cache key is the signature object itself, so caching before
        # signing cannot go stale). Callers treat the dict as read-only.
        sig = self.signature
        if self.__dict__.get("_obj_sig") is sig:
            return self.__dict__["_obj"]
        o = {
            "validator_address": self.validator_address.hex(),
            "validator_index": self.validator_index,
            "height": self.height,
            "round": self.round,
            "timestamp_ns": self.timestamp_ns,
            "type": self.type,
            "block_id": self.block_id.to_obj(),
            "signature": sig.hex(),
        }
        self.__dict__["_obj"] = o
        self.__dict__["_obj_sig"] = sig
        return o

    @classmethod
    def from_obj(cls, o, block_id=None) -> "Vote":
        """`block_id`: the BlockID already built for o["block_id"]'s
        fields (Commit.from_obj hands every vote of one block the same
        object); a single gossiped vote builds its own."""
        if block_id is None:
            from tendermint_tpu.types.block import BlockID
            block_id = BlockID.from_obj(o["block_id"])
        return cls(
            validator_address=bytes.fromhex(o["validator_address"]),
            validator_index=o["validator_index"],
            height=o["height"], round=o["round"],
            timestamp_ns=o["timestamp_ns"], type=o["type"],
            block_id=block_id,
            signature=bytes.fromhex(o["signature"]))

    def verify(self, chain_id: str, pubkey: bytes) -> bool:
        """Scalar path (types/vote.go:109). Hot paths batch via VoteSet."""
        if address_of(pubkey) != self.validator_address:
            return False
        from tendermint_tpu.utils import ed25519_ref as ref
        return ref.verify(pubkey, self.sign_bytes(chain_id), self.signature)

    def validate_basic(self) -> None:
        if not VoteType.valid(self.type):
            raise ValueError(f"invalid vote type {self.type}")
        if self.height < 1 or self.round < 0:
            raise ValueError("invalid height/round")
        if len(self.validator_address) != 20:
            raise ValueError("bad validator address")
        if self.validator_index < 0:
            raise ValueError("bad validator index")

    def __str__(self) -> str:
        t = "prevote" if self.type == VoteType.PREVOTE else "precommit"
        return (f"Vote{{{self.validator_index}:{self.validator_address.hex()[:8]} "
                f"{self.height}/{self.round} {t} {self.block_id.short()}}}")
