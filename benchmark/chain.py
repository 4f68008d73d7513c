"""Chains made from --seed, held as wire bytes.

Copied from bench_fastsync.ChainBuilder and chip_smoke.lite_chain (the
originals stay where they are; PERF.md lists them for a later PR to
delete) and changed in three ways: the seed sets every key, chain id
and transaction value; a transaction has a stated size (`tx_bytes`)
instead of the originals' ~14 bytes; and what is handed on is wire
bytes, few and large, so the harness keeps no graph of Python objects
alive while the node runs. A seed changes contents and never sizes:
the same validator count, all signing, the same number and size of
transactions and the same chain length for every seed.
"""

from __future__ import annotations

import hashlib
import random
from typing import List, Tuple

from benchmark.kvref import openssl_signer


def _validator_seeds(seed: int, n_vals: int, tag: str) -> List[bytes]:
    rng = random.Random(f"{seed}/{tag}/keys")
    return [rng.randbytes(32) for _ in range(n_vals)]


def chain_id_of(tag: str, seed: int) -> str:
    """The same length for every seed: a chain id is in every header
    and every vote's sign bytes."""
    return f"bench-{tag}-{seed % 10 ** 12:012d}"


def padded_tx(key: bytes, value: bytes, pad: bytes, off: int,
              tx_bytes: int) -> bytes:
    """`key=value.` padded with printable bytes from `pad` to exactly
    tx_bytes (never cut below key=value)."""
    head = key + b"=" + value + b"."
    need = tx_bytes - len(head)
    if need <= 0:
        return head
    off %= max(1, len(pad) - need)
    return head + pad[off:off + need]


def pad_blob(seed: int, tag: str, size: int) -> bytes:
    """Printable filler drawn once from the seed; transactions take
    slices of it."""
    return hashlib.shake_256(f"{seed}/{tag}/pad".encode()).hexdigest(
        size // 2 + 1).encode()[:size]


class ChainBuilder:
    """Streamed generation of a valid chain: build_wire(n) returns the
    next n blocks as wire bytes with each block's (hash, app hash),
    carrying app and state forward. Keys cycle over `key_space` heights
    (overwrites, a bounded working set). Blocks are applied through the
    program's own serial executor so that headers embed real app
    hashes; kvref.PlainKV checks those hashes after the window."""

    def __init__(self, seed: int, n_vals: int, n_txs: int, tx_bytes: int,
                 key_space: int = 512):
        from tendermint_tpu.abci.apps import KVStoreApp
        from tendermint_tpu.abci.proxy import AppConns, local_client_creator
        from tendermint_tpu.abci.types import ValidatorUpdate
        from tendermint_tpu.storage import MemDB, StateStore
        from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
        from tendermint_tpu.types.block import Commit

        seeds = _validator_seeds(seed, n_vals, "sync")
        keys = [PrivKey.generate(s) for s in seeds]
        self.signers = {k.pubkey.address: openssl_signer(s).sign
                        for k, s in zip(keys, seeds)}
        self.gen = GenesisDoc(
            chain_id=chain_id_of("sync", seed), genesis_time_ns=1,
            validators=[GenesisValidator(k.pubkey.ed25519, 10)
                        for k in keys])
        self.state = StateStore(MemDB()).load_or_genesis(self.gen)
        self.conns = AppConns(local_client_creator(KVStoreApp()))
        self.conns.consensus.init_chain(
            [ValidatorUpdate(v.pubkey, v.voting_power)
             for v in self.state.validators.validators], self.gen.chain_id)
        self.n_txs, self.tx_bytes, self.key_space = n_txs, tx_bytes, key_space
        self.part_size = \
            self.state.consensus_params.block_gossip.block_part_size_bytes
        self.height = 0
        self.last_commit = Commit()
        self._pad = pad_blob(seed, "sync", 4 * tx_bytes + n_txs + 4096)

    def txs_of(self, h: int) -> List[bytes]:
        pad, size = self._pad, self.tx_bytes
        slot = h % self.key_space
        return [padded_tx(b"k%d.%d" % (slot, i), b"v%d" % h, pad,
                          7 * h + i, size) for i in range(self.n_txs)]

    def build_wire(self, n: int, with_txs: bool = True
                   ) -> Tuple[List[bytes], List[Tuple[bytes, bytes]]]:
        """The next n blocks: ([wire bytes], [(block hash, header app
        hash)]). `with_txs` False makes them empty (the sentinel that
        only lends its LastCommit)."""
        from tendermint_tpu.state.execution import (exec_block_on_app,
                                                    update_state)
        from tendermint_tpu.types.block import BlockID, Commit
        from tendermint_tpu.types.vote import Vote, VoteType

        wire, expect = [], []
        for _ in range(n):
            h = self.height + 1
            txs = self.txs_of(h) if with_txs else []
            block = self.state.make_block(h, txs, self.last_commit,
                                          time_ns=h * 10 ** 9)
            parts = block.make_part_set(self.part_size)
            block_id = BlockID(block.hash(), parts.header())
            wire.append(block.to_bytes())
            expect.append((block.hash(), block.header.app_hash))
            precommits, msg = [], None
            for idx, val in enumerate(self.state.validators.validators):
                v = Vote(validator_address=val.address,
                         validator_index=idx, height=h, round=0,
                         timestamp_ns=h * 10 ** 9 + 1,
                         type=VoteType.PRECOMMIT, block_id=block_id)
                if msg is None:
                    # one timestamp + one block id: every validator
                    # signs identical canonical bytes for this block
                    msg = v.sign_bytes(self.gen.chain_id)
                v.signature = self.signers[val.address](msg)
                precommits.append(v)
            self.last_commit = Commit(block_id, precommits)
            responses = exec_block_on_app(self.conns.consensus, block,
                                          self.state.validators)
            new_state = update_state(self.state.copy(), block_id, block,
                                     responses)
            new_state.app_hash = self.conns.consensus.commit()
            self.state = new_state
            self.height = h
        return wire, expect


def forge_precommit(wire_block: bytes, which: int) -> bytes:
    """The block's wire bytes with one bit of one LastCommit signature
    flipped (and the header's hash of that commit made to match): the
    commit FOR the block below it is forged."""
    from tendermint_tpu.types import encoding
    from tendermint_tpu.types.block import Block
    blk = Block.from_bytes(wire_block)
    votes = blk.last_commit.precommits
    vote = votes[which % len(votes)]
    vote.signature = vote.signature[:40] + bytes(
        [vote.signature[40] ^ 1]) + vote.signature[41:]
    # the block stays consistent with itself, so that nothing but
    # verifying the signature can tell
    blk.header.last_commit_hash = blk.last_commit.hash()
    return encoding.cdumps(blk.to_obj())


# ------------------------------------------------------------ lite chain

class LiteChain:
    """n_headers consecutive signed headers of one constant validator
    set, every precommit signed by ops/ed25519.sign_batch (the device
    on a TPU), held as wire bytes: one per signed header, and the
    validator set once."""

    def __init__(self, seed: int, n_headers: int, n_vals: int,
                 sign: str = "device"):
        from tendermint_tpu.lite.types import SignedHeader
        from tendermint_tpu.ops import ed25519
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.block import (BlockID, Commit, Header,
                                                PartSetHeader)
        from tendermint_tpu.types.validator_set import (Validator,
                                                        ValidatorSet)
        from tendermint_tpu.types.vote import Vote, VoteType

        self.chain_id = chain_id_of("lite", seed)
        self.n_headers, self.n_vals = n_headers, n_vals
        seed_of = {}
        for s in _validator_seeds(seed, n_vals, "lite"):
            pub = openssl_signer(s).public_key().public_bytes_raw()
            seed_of[pub] = s
        valset = ValidatorSet([Validator(pk, 10) for pk in seed_of])
        vals = valset.validators
        self.seeds = [seed_of[v.pubkey] for v in vals]
        self.pubkeys = [v.pubkey for v in vals]
        vhash = valset.hash()
        parts = PartSetHeader(1, hashlib.sha256(b"lite-parts").digest())
        rng = random.Random(f"{seed}/lite/app")
        headers, bids, self.msgs = [], [], []
        for h in range(1, n_headers + 1):
            header = Header(chain_id=self.chain_id, height=h, time_ns=h,
                            validators_hash=vhash,
                            app_hash=rng.randbytes(32))
            bid = BlockID(header.hash(), parts)
            headers.append(header)
            bids.append(bid)
            # v0.16 sign bytes carry no validator identity and the
            # votes share one timestamp: all sign the same bytes
            self.msgs.append(Vote(vals[0].address, 0, h, 0, h,
                                  VoteType.PRECOMMIT,
                                  bid).sign_bytes(self.chain_id))
        if sign == "device":
            self.sigs = ed25519.sign_batch(
                [s for _ in range(n_headers) for s in self.seeds],
                [m for m in self.msgs for _ in range(n_vals)])
        else:       # a toy chain that must not compile the sign kernel
            signers = [openssl_signer(s).sign for s in self.seeds]
            self.sigs = [sg(m) for m in self.msgs for sg in signers]
        self.valset_wire = encoding.cdumps(valset.to_obj())
        self.wire: List[bytes] = []
        for i, h in enumerate(range(1, n_headers + 1)):
            precommits = []
            for j, val in enumerate(vals):
                v = Vote(val.address, j, h, 0, h, VoteType.PRECOMMIT,
                         bids[i])
                v.signature = self.sigs[i * n_vals + j]
                precommits.append(v)
            self.wire.append(encoding.cdumps(SignedHeader(
                headers[i], Commit(bids[i], precommits), bids[i]).to_obj()))

    def decode(self, wire: List[bytes] = None):
        """(valset, [FullCommit]) fresh from the wire bytes: what a
        light client holds after its provider answered."""
        from tendermint_tpu.lite.types import FullCommit, SignedHeader
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.validator_set import ValidatorSet
        valset = ValidatorSet.from_obj(encoding.cloads(self.valset_wire))
        loads, from_obj = encoding.cloads, SignedHeader.from_obj
        return valset, [FullCommit(from_obj(loads(raw)), valset)
                        for raw in (self.wire if wire is None else wire)]

    def forged_header(self, height: int) -> bytes:
        """A header nobody signed at `height`, dressed in the genuine
        commit's signatures, as wire bytes."""
        from tendermint_tpu.lite.types import SignedHeader
        from tendermint_tpu.types import encoding
        from tendermint_tpu.types.block import BlockID, Commit, Header
        from tendermint_tpu.types.vote import Vote
        real = SignedHeader.from_obj(encoding.cloads(self.wire[height - 1]))
        header = Header(chain_id=self.chain_id, height=height,
                        time_ns=height,
                        validators_hash=real.header.validators_hash,
                        app_hash=b"\xff" * 32)
        bid = BlockID(header.hash(), real.block_id.parts)
        votes = [Vote(v.validator_address, v.validator_index, v.height,
                      v.round, v.timestamp_ns, v.type, bid, v.signature)
                 for v in real.commit.precommits]
        return encoding.cdumps(
            SignedHeader(header, Commit(bid, votes), bid).to_obj())
