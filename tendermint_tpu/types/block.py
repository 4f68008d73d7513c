"""Block, Header, Commit, BlockID — capability parity with types/block.go.

Hashing: every structural hash is the SHA-256 Merkle spec (ops/merkle.py).
Header.hash is a Merkle root over the canonical field map (the reference
does a merkle-map of 13 fields, types/block.go:178-197); Commit.hash and
Data.hash are Merkle roots over items; Block serialization is canonical
JSON, split into PartSets for gossip.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from tendermint_tpu import telemetry
from tendermint_tpu.ops import merkle
from tendermint_tpu.telemetry import trace
from tendermint_tpu.types import encoding
from tendermint_tpu.types.vote import Vote, VoteType

# counted once per commit decoded (Commit.from_obj), with its two totals
_m_block_ids = telemetry.counter(
    "verifier_commit_block_ids_total",
    "BlockID objects of commits decoded from wire objects: built (one "
    "per distinct block id of a commit, the commit's own included) or "
    "shared (a vote handed one already built for its commit)", ("how",))

# counted once per block decoded from wire bytes (Block.from_wire)
_m_block_decodes = telemetry.counter(
    "wire_block_decodes_total",
    "Blocks decoded from wire bytes: native (the transaction list "
    "filled from the wire's hex by native/codec.cpp, the rest by "
    "json.loads) or pure (the whole document by json.loads and "
    "bytes.fromhex: no extension, or a document it raised Fallback "
    "for)", ("how",))

# where a block's wire object keeps its transactions
TXS_PATH = ("data", "txs")


@dataclass
class PartSetHeader:
    total: int = 0
    hash: bytes = b""

    def is_zero(self) -> bool:
        return self.total == 0 and not self.hash

    def to_obj(self):
        return {"total": self.total, "hash": self.hash.hex()}

    @classmethod
    def from_obj(cls, o):
        return cls(o["total"], bytes.fromhex(o["hash"]))

    def __eq__(self, other):
        return isinstance(other, PartSetHeader) and \
            (self.total, self.hash) == (other.total, other.hash)


@dataclass
class BlockID:
    hash: bytes = b""
    parts: PartSetHeader = field(default_factory=PartSetHeader)

    def is_zero(self) -> bool:
        return not self.hash and self.parts.is_zero()

    def __setattr__(self, name, value):
        # field writes invalidate the cached key string (nested
        # parts-field mutation is not covered; parts are replaced, not
        # mutated, everywhere in the codebase)
        if not name.startswith("_"):
            self.__dict__.pop("_key", None)
        object.__setattr__(self, name, value)

    def key(self) -> str:
        # cached: key() is called per vote on hot paths (dict keys,
        # equality in the reference idiom) and hexes 64 bytes each time
        k = self.__dict__.get("_key")
        if k is None:
            k = (self.hash.hex() + "/" + str(self.parts.total) + "/"
                 + self.parts.hash.hex())
            self.__dict__["_key"] = k
        return k

    def short(self) -> str:
        return self.hash.hex()[:8] if self.hash else "<nil>"

    def to_obj(self):
        return {"hash": self.hash.hex(), "parts": self.parts.to_obj()}

    @classmethod
    def from_obj(cls, o):
        return cls(bytes.fromhex(o["hash"]), PartSetHeader.from_obj(o["parts"]))

    def __eq__(self, other):
        # raw field compare — no hex round-trip on the hot path
        return isinstance(other, BlockID) and self.hash == other.hash \
            and self.parts.total == other.parts.total \
            and self.parts.hash == other.parts.hash

    def __hash__(self):
        return hash((self.hash, self.parts.total, self.parts.hash))


@dataclass
class Header:
    chain_id: str = ""
    height: int = 0
    time_ns: int = 0
    num_txs: int = 0
    total_txs: int = 0
    last_block_id: BlockID = field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""

    def to_obj(self):
        return {
            "chain_id": self.chain_id, "height": self.height,
            "time_ns": self.time_ns, "num_txs": self.num_txs,
            "total_txs": self.total_txs,
            "last_block_id": self.last_block_id.to_obj(),
            "last_commit_hash": self.last_commit_hash.hex(),
            "data_hash": self.data_hash.hex(),
            "validators_hash": self.validators_hash.hex(),
            "consensus_hash": self.consensus_hash.hex(),
            "app_hash": self.app_hash.hex(),
            "last_results_hash": self.last_results_hash.hex(),
            "evidence_hash": self.evidence_hash.hex(),
        }

    @classmethod
    def from_obj(cls, o):
        return cls(
            chain_id=o["chain_id"], height=o["height"], time_ns=o["time_ns"],
            num_txs=o["num_txs"], total_txs=o["total_txs"],
            last_block_id=BlockID.from_obj(o["last_block_id"]),
            last_commit_hash=bytes.fromhex(o["last_commit_hash"]),
            data_hash=bytes.fromhex(o["data_hash"]),
            validators_hash=bytes.fromhex(o["validators_hash"]),
            consensus_hash=bytes.fromhex(o["consensus_hash"]),
            app_hash=bytes.fromhex(o["app_hash"]),
            last_results_hash=bytes.fromhex(o["last_results_hash"]),
            evidence_hash=bytes.fromhex(o["evidence_hash"]))

    def __setattr__(self, name, value):
        # ANY field write invalidates the cached hash — headers are
        # mutated during fill_header and by tamper-style tests; a stale
        # hash here would be a consensus bug
        if not name.startswith("_"):
            self.__dict__.pop("_hash", None)
        object.__setattr__(self, name, value)

    def hash(self) -> bytes:
        """Merkle root over sorted (field, value) leaves — the merkle-map of
        types/block.go:178. Empty validators_hash => zero hash (unfilled).

        Cached (invalidated by __setattr__ on any field write):
        fast-sync/store/validate hash the same header several times per
        block, and each hash is 13 canonical encodes + a Merkle tree."""
        if not self.validators_hash:
            return b""
        h = self.__dict__.get("_hash")
        if h is None:
            obj = self.to_obj()
            leaves = [encoding.cdumps({k: obj[k]}) for k in sorted(obj)]
            h = merkle.root_host(leaves)
            self.__dict__["_hash"] = h
        return h


@dataclass
class Data:
    txs: List[bytes] = field(default_factory=list)

    def hash(self) -> bytes:
        # cached behind a tuple fingerprint of the tx objects: the
        # tuple HOLDS references, so object ids stay valid for the
        # cache's lifetime and the comparison short-circuits on
        # identity — a 5,000-tx root is ~15k SHA compressions, the
        # fingerprint check ~100us. Replacing a tx yields a different
        # object => different fingerprint => recompute (the reference
        # memoizes Data.Hash the same way, types/block.go:472-478,
        # with no fingerprint at all).
        fp = tuple(self.txs)
        cached = self.__dict__.get("_hash_fp")
        if cached is not None and cached[0] == fp:
            return cached[1]
        h = merkle.root_host(list(fp))
        self.__dict__["_hash_fp"] = (fp, h)
        return h

    def to_obj(self):
        return {"txs": [t.hex() for t in self.txs]}

    @classmethod
    def from_obj(cls, o):
        return cls([bytes.fromhex(t) for t in o["txs"]])


@dataclass
class Commit:
    """+2/3 precommits for a block (types/block.go:239). precommits[i] is
    None when validator i did not precommit (absent)."""
    block_id: BlockID = field(default_factory=BlockID)
    precommits: List[Optional[Vote]] = field(default_factory=list)

    def height(self) -> int:
        for v in self.precommits:
            if v is not None:
                return v.height
        return 0

    def round(self) -> int:
        for v in self.precommits:
            if v is not None:
                return v.round
        return 0

    def size(self) -> int:
        return len(self.precommits)

    def is_commit(self) -> bool:
        return len(self.precommits) > 0

    def validate_basic(self) -> None:
        """types/block.go:322 semantics."""
        if self.block_id.is_zero():
            raise ValueError("commit cannot be for nil block")
        if not any(v is not None for v in self.precommits):
            raise ValueError("no precommits in commit")
        h, r = self.height(), self.round()
        for v in self.precommits:
            if v is None:
                continue
            if v.type != VoteType.PRECOMMIT:
                raise ValueError("commit contains non-precommit vote")
            if v.height != h or v.round != r:
                raise ValueError("commit votes differ in height/round")

    def __setattr__(self, name, value):
        # same contract as Header: ANY field write drops the cached
        # hash/obj, so a mutated commit can never serve stale bytes
        if not name.startswith("_"):
            self.__dict__.pop("_hash", None)
            self.__dict__.pop("_obj", None)
            self.__dict__.pop("_cbytes", None)
            self.__dict__.pop("_fp", None)
        object.__setattr__(self, name, value)

    def _check_cache_fresh(self) -> None:
        # __setattr__ can't see IN-PLACE mutation (precommits[i].signature
        # = ..., the tamper-test idiom), so the caches are additionally
        # keyed on a fingerprint of every sign-relevant vote field plus
        # the commit's own block id — tuple compares over raw bytes/ints
        # (no hexing), far cheaper than the O(V) encodes they guard
        fp = (self.block_id.hash, self.block_id.parts.total,
              self.block_id.parts.hash,
              tuple((v.signature, v.timestamp_ns, v.height, v.round,
                     int(v.type), v.validator_address, v.validator_index,
                     v.block_id.hash, v.block_id.parts.total,
                     v.block_id.parts.hash)
                    if v is not None else None
                    for v in self.precommits))
        if self.__dict__.get("_fp") != fp:
            self.__dict__.pop("_hash", None)
            self.__dict__.pop("_obj", None)
            self.__dict__.pop("_cbytes", None)
            self.__dict__["_fp"] = fp

    def hash(self) -> bytes:
        # cached: the sync loop hashes the same commit for validate_basic
        # + header checks + store meta — O(V) encodes each time at V
        # validators; invalidation via __setattr__ + _check_cache_fresh
        self._check_cache_fresh()
        if "_hash" not in self.__dict__:
            leaves = [encoding.cdumps(v.to_obj() if v else None)
                      for v in self.precommits]
            self.__dict__["_hash"] = merkle.root_host(leaves)
        return self.__dict__["_hash"]

    def to_obj(self):
        self._check_cache_fresh()
        if "_obj" not in self.__dict__:
            self.__dict__["_obj"] = {
                "block_id": self.block_id.to_obj(),
                "precommits": [v.to_obj() if v else None
                               for v in self.precommits]}
        return self.__dict__["_obj"]

    def to_bytes(self) -> bytes:
        # cached canonical encoding (same invalidation contract as
        # hash()): the store writes each commit twice per height
        # (last_commit + seen_commit of adjacent blocks) and each encode
        # walks V vote objects
        self._check_cache_fresh()
        b = self.__dict__.get("_cbytes")
        if b is None:
            b = encoding.cdumps(self.to_obj())
            self.__dict__["_cbytes"] = b
        return b

    @classmethod
    def from_obj(cls, o):
        """One BlockID per distinct block id of THIS commit: a vote
        whose block id has the wire fields of one already built (the
        commit's own first) is handed that object, as a locally built
        commit's votes are, and no BlockID, PartSetHeader or hex
        conversion is made for it. The table dies with the call. Its
        keys are the fields as they stand in `o`, and the type of
        `total`: 1 == 1.0 == True, and only 1 signs as "total":1."""
        b = o["block_id"]
        parts = b["parts"]
        total = parts["total"]
        block_id = BlockID.from_obj(b)
        built = {(b["hash"], parts["hash"], total, type(total)): block_id}
        precommits = []
        shared = 0
        for v in o["precommits"]:
            if not v:
                precommits.append(None)
                continue
            b = v["block_id"]
            parts = b["parts"]
            total = parts["total"]
            key = (b["hash"], parts["hash"], total, type(total))
            bid = built.get(key)
            if bid is None:
                bid = built[key] = BlockID.from_obj(b)
            else:
                shared += 1
            precommits.append(Vote.from_obj(v, bid))
        if telemetry.enabled():
            _m_block_ids.labels("built").inc(len(built))
            _m_block_ids.labels("shared").inc(shared)
        return cls(block_id, precommits)


@dataclass
class EvidenceData:
    evidence: list = field(default_factory=list)

    def hash(self) -> bytes:
        return merkle.root_host([encoding.cdumps(e.to_obj()) for e in self.evidence])

    def to_obj(self):
        from tendermint_tpu.types.evidence import evidence_to_obj
        return {"evidence": [evidence_to_obj(e) for e in self.evidence]}

    @classmethod
    def from_obj(cls, o):
        from tendermint_tpu.types.evidence import evidence_from_obj
        return cls([evidence_from_obj(e) for e in o["evidence"]])


@dataclass
class Block:
    header: Header
    data: Data = field(default_factory=Data)
    evidence: EvidenceData = field(default_factory=EvidenceData)
    last_commit: Commit = field(default_factory=Commit)

    def fill_header(self) -> None:
        """Populate derived header hashes (types/block.go:74). Cache
        invalidation is automatic: the field writes go through
        Header.__setattr__ (dropping the header-hash cache), and the
        block-bytes cache below is keyed on the header hash."""
        h = self.header
        if not h.last_commit_hash:
            h.last_commit_hash = self.last_commit.hash()
        if not h.data_hash:
            h.data_hash = self.data.hash()
        if not h.evidence_hash:
            h.evidence_hash = self.evidence.hash()

    def validate_basic(self) -> None:
        """Self-consistency (types/block.go:51)."""
        if self.header.height < 1:
            raise ValueError("invalid block height")
        if self.header.num_txs != len(self.data.txs):
            raise ValueError("num_txs mismatch")
        if self.header.height > 1:
            self.last_commit.validate_basic()
        if self.header.last_commit_hash != self.last_commit.hash():
            raise ValueError("last_commit_hash mismatch")
        if self.header.data_hash != self.data.hash():
            raise ValueError("data_hash mismatch")
        if self.header.evidence_hash != self.evidence.hash():
            raise ValueError("evidence_hash mismatch")

    def hash(self) -> bytes:
        self.fill_header()
        return self.header.hash()

    def to_obj(self):
        return {"header": self.header.to_obj(), "data": self.data.to_obj(),
                "evidence": self.evidence.to_obj(),
                "last_commit": self.last_commit.to_obj()}

    @classmethod
    def from_obj(cls, o):
        return cls(Header.from_obj(o["header"]), Data.from_obj(o["data"]),
                   EvidenceData.from_obj(o["evidence"]),
                   Commit.from_obj(o["last_commit"]))

    def to_bytes(self) -> bytes:
        # cached KEYED ON THE HEADER HASH: the sync loop serializes each
        # block for the part set while the store serializes it again,
        # and blocks parsed from the wire keep their original bytes for
        # free. Header mutations auto-invalidate the header hash (its
        # __setattr__), which invalidates this cache transitively — so
        # tampering with a cached block cannot yield bytes that disagree
        # with its hash. (Mutating data/evidence/last_commit WITHOUT the
        # header changing was already an inconsistent block before any
        # caching: the header's derived hashes would be stale.)
        hh = self.header.hash()
        if (self.__dict__.get("_bytes_hh") == hh
                and self.__dict__.get("_bytes") is not None):
            return self.__dict__["_bytes"]
        b = encoding.cdumps(self.to_obj())
        self.__dict__["_bytes"] = b
        self.__dict__["_bytes_hh"] = hh
        return b

    @classmethod
    def from_wire(cls, o, txs: Optional[List[bytes]]) -> "Block":
        """The block of a wire document as
        encoding.cloads_hex_array(doc, <keys of the block> + TXS_PATH)
        left it: `o` the block's object and `txs` its transactions,
        already bytes, or None where `o` still holds them as hex
        (from_obj, the specification, decodes them). Every decode of a
        block from wire bytes comes through here."""
        if txs is None:
            blk = cls.from_obj(o)
        else:
            blk = cls(Header.from_obj(o["header"]), Data(txs),
                      EvidenceData.from_obj(o["evidence"]),
                      Commit.from_obj(o["last_commit"]))
        if telemetry.enabled():
            _m_block_decodes.labels(
                "pure" if txs is None else "native").inc()
        return blk

    @classmethod
    def from_bytes(cls, b: bytes) -> "Block":
        with trace.span("wire.decode_block", bytes=len(b)):
            blk = cls.from_wire(*encoding.cloads_hex_array(b, TXS_PATH))
            blk.__dict__["_bytes"] = bytes(b)
            blk.__dict__["_bytes_hh"] = blk.header.hash()
        return blk

    def make_part_set(self, part_size: int):
        # cached KEYED ON (HEADER HASH, PART SIZE), the same
        # invalidation discipline as to_bytes above: block_id() used to
        # re-serialize, re-split and re-hash the whole block on every
        # call. A header mutation changes the header hash (its
        # __setattr__ drops the cached hash), which misses this key and
        # rebuilds — a tampered block can never serve a stale part set.
        # Unfilled headers (hash() == b"") are never cached: their hash
        # cannot witness further mutation.
        from tendermint_tpu.types.part_set import PartSet
        hh = self.header.hash()
        if hh and self.__dict__.get("_partset_key") == (hh, part_size):
            return self.__dict__["_partset"]
        ps = PartSet.from_data(self.to_bytes(), part_size)
        if hh:
            self.__dict__["_partset"] = ps
            self.__dict__["_partset_key"] = (hh, part_size)
        return ps

    def block_id(self, part_size: int) -> BlockID:
        ps = self.make_part_set(part_size)
        return BlockID(self.hash(), ps.header())
