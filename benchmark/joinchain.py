"""A block chain whose validator set moves THROUGH THE APPLICATION, made
from --seed and held as wire bytes: what a peer serves a full node that
joins a public proof-of-stake chain by fast-sync.

`n_vals` validators hold stake `stake_scale // (r + 2)` by rank r =
1..n_vals, rank dealt by a seeded shuffle (benchmark/configs/
net_100v.json's law); the genesis carries that set. Every later set is
the work of `val:<pubkey>/<power>` transactions inside blocks, as the
KVStore app (abci's persistent kvstore example) takes them: EndBlock at
height h returns the block's updates and they are in force from h + 1.
At seeded heights, none at height 1, a block carries ONE change besides
its key=value transactions: a stake change (one seeded validator's power
moves by a seeded 1-5%, up or down: one `val:` transaction) or a
membership change (the validator of least stake leaves, a standby key
joins with that stake plus one: two `val:` transactions, one
update_with_changes), so the set stays at its cap. benchmark/
churnchain.py's law, with the changes carried by transactions and not
by the builder's hand.

Blocks are made and applied through the program's own serial executor
(State.make_block, exec_block_on_app, update_state), so headers carry
real app hashes, real `validators_hash`es and real results hashes;
benchmark/joinref.py replays them with `json` and hashlib alone. Every
member of the set in force signs every commit (OpenSSL), each precommit
with a timestamp of its own (height x 1e9 + index ns), so a block's
commit brings `n_vals` distinct sign-bytes.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Tuple

from benchmark.chain import (_validator_seeds, chain_id_of, pad_blob,
                             padded_tx)
from benchmark.kvref import openssl_signer

STAKE, MEMBERSHIP = "stake", "membership"


def val_tx(pubkey: bytes, power: int) -> bytes:
    return b"val:%s/%d" % (pubkey.hex().encode(), power)


class JoinChain:
    """`n_blocks` blocks and a sentinel that only lends its LastCommit,
    as `wire`; each block's (hash, header app hash) as `expect`;
    `change_at[h]` says what block h carries besides its transactions;
    `genesis_wire` is the genesis document a node starts from.

    `cut_val_at`, for the tampered copy alone: the block at that height
    leaves its `val:` transaction out while the chain's validators move
    to the set it would have made all the same, so the header above it
    names a set no node that executes the blocks arrives at. Everything
    else of that copy is consistent and signed; it ends two blocks
    above the cut."""

    def __init__(self, seed: int, n_blocks: int, n_vals: int,
                 stake_changes: int, membership_changes: int, n_txs: int,
                 tx_bytes: int, key_space: int,
                 stake_scale: int = 1_000_000,
                 cut_val_at: Optional[int] = None):
        from tendermint_tpu.abci.apps import KVStoreApp
        from tendermint_tpu.abci.proxy import AppConns, local_client_creator
        from tendermint_tpu.abci.types import ValidatorUpdate
        from tendermint_tpu.storage import MemDB, StateStore
        from tendermint_tpu.types import (GenesisDoc, GenesisValidator,
                                          encoding)
        from tendermint_tpu.types.block import Commit

        self.n_txs, self.tx_bytes, self.key_space = n_txs, tx_bytes, key_space
        self._rng = random.Random(f"{seed}/join/sets")
        self._signer: Dict[bytes, object] = {}      # pubkey -> sign(msg)
        for s in _validator_seeds(seed, n_vals + membership_changes, "join"):
            key = openssl_signer(s)
            self._signer[key.public_key().public_bytes_raw()] = key.sign
        pubs = list(self._signer)
        self._standby = pubs[n_vals:]
        ranks = list(range(1, n_vals + 1))
        self._rng.shuffle(ranks)
        self.gen = GenesisDoc(
            chain_id=chain_id_of("join", seed), genesis_time_ns=1,
            validators=[GenesisValidator(pk, stake_scale // (r + 2))
                        for pk, r in zip(pubs, ranks)])
        self.genesis_wire = encoding.cdumps(self.gen.to_obj())
        self.change_at = self._place_changes(n_blocks, stake_changes,
                                             membership_changes)
        self.joined_at: Dict[int, Tuple[bytes, bytes]] = {}
        #   h -> (the key that left, the key that joined) by block h

        self._state = StateStore(MemDB()).load_or_genesis(self.gen)
        self._conns = AppConns(local_client_creator(KVStoreApp()))
        self._conns.consensus.init_chain(
            [ValidatorUpdate(v.pubkey, v.voting_power)
             for v in self._state.validators.validators], self.gen.chain_id)
        self._part_size = \
            self._state.consensus_params.block_gossip.block_part_size_bytes
        self._pad = pad_blob(seed, "join", 4 * tx_bytes + n_txs + 4096)
        self._last_commit = Commit()
        self.wire: List[bytes] = []
        self.expect: List[Tuple[bytes, bytes]] = []
        upto = n_blocks if cut_val_at is None else cut_val_at + 1
        self.n_sigs = sum(           # over the commits of blocks 1..upto
            self._build(h, self._txs_of(h) + self._val_txs(h),
                        cut=(h == cut_val_at)) for h in range(1, upto + 1))
        self._build(upto + 1, [])               # the sentinel
        del (self._state, self._conns, self._last_commit, self._pad,
             self._rng, self._standby)

    def _place_changes(self, n_blocks: int, stake_changes: int,
                       membership_changes: int) -> Dict[int, str]:
        """Which block carries what: distinct seeded heights in
        2..n_blocks."""
        heights = self._rng.sample(range(2, n_blocks + 1),
                                   stake_changes + membership_changes)
        at = {h: MEMBERSHIP for h in heights[:membership_changes]}
        at.update((h, STAKE) for h in heights[membership_changes:])
        return at

    # ------------------------------------------------------------ a block

    def _txs_of(self, h: int) -> List[bytes]:
        slot = h % self.key_space
        return [padded_tx(b"k%d.%d" % (slot, i), b"v%d" % h, self._pad,
                          7 * h + i, self.tx_bytes)
                for i in range(self.n_txs)]

    def _val_txs(self, h: int) -> List[bytes]:
        """The change block h carries, drawn against the set in force at
        h (the newest there is: an update at h - 1 is in force by now)."""
        kind, rng = self.change_at.get(h), self._rng
        vals = self._state.validators.validators
        if kind == STAKE:
            v = rng.choice(vals)
            step = max(1, v.voting_power * rng.randint(1, 5) // 100)
            if rng.random() < 0.5 and v.voting_power > step:
                step = -step
            return [val_tx(v.pubkey, v.voting_power + step)]
        if kind == MEMBERSHIP:
            out = min(vals, key=lambda v: (v.voting_power, v.address))
            new = self._standby.pop(0)
            self.joined_at[h] = (out.pubkey, new)
            return [val_tx(out.pubkey, 0),
                    val_tx(new, out.voting_power + 1)]
        return []

    def _build(self, h: int, txs: List[bytes], cut: bool = False) -> int:
        """Make block h of `txs`, sign its commit, apply it; how many
        signed."""
        from tendermint_tpu.abci.types import ValidatorUpdate
        from tendermint_tpu.state.execution import (exec_block_on_app,
                                                    update_state)
        from tendermint_tpu.types.block import BlockID, Commit
        from tendermint_tpu.types.vote import (Vote, VoteType,
                                               sign_bytes_template)
        state = self._state
        left_out = [t for t in txs if t.startswith(b"val:")] if cut else []
        block = state.make_block(
            h, [t for t in txs if t not in left_out], self._last_commit,
            time_ns=h * 10 ** 9)
        parts = block.make_part_set(self._part_size)
        block_id = BlockID(block.hash(), parts.header())
        self.wire.append(block.to_bytes())
        self.expect.append((block.hash(), block.header.app_hash))
        pre, suf = sign_bytes_template(state.chain_id, block_id, h, 0,
                                       VoteType.PRECOMMIT)
        precommits = []
        for idx, val in enumerate(state.validators.validators):
            ts = h * 10 ** 9 + idx
            precommits.append(Vote(
                val.address, idx, h, 0, ts, VoteType.PRECOMMIT, block_id,
                self._signer[val.pubkey]((pre + str(ts) + suf).encode())))
        self._last_commit = Commit(block_id, precommits)
        responses = exec_block_on_app(self._conns.consensus, block,
                                      state.validators)
        for tx in left_out:     # the validators move all the same
            pk_hex, _, power = tx[4:].partition(b"/")
            responses.end_block_obj.setdefault("validator_updates", []).append(
                ValidatorUpdate(bytes.fromhex(pk_hex.decode()),
                                int(power)).to_obj())
        new_state = update_state(state, block_id, block, responses)
        new_state.app_hash = self._conns.consensus.commit()
        self._state = new_state
        return len(precommits)


# --------------------------------------------------------- tampered copies

def departed_signs_for_joiner(chain: JoinChain, change_height: int
                              ) -> Tuple[int, List[bytes]]:
    """(the height tampered with, the chain's wire cut two blocks above
    it): at the first height after the join that block `change_height`
    carries, the joiner's precommit bears the DEPARTED key's signature
    over the joiner's own sign-bytes (and the header's hash of that
    commit is made to match, so that nothing but verifying the
    signature under the right key can tell). A node that judged that
    commit under the set it held before the change would take it."""
    from tendermint_tpu.types import encoding
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.keys import address_of
    departed, joiner = chain.joined_at[change_height]
    at = change_height + 1
    wire = list(chain.wire[:at + 1])
    blk = Block.from_bytes(wire[at])        # carries the commit for `at`
    vote, = [v for v in blk.last_commit.precommits
             if v.validator_address == address_of(joiner)]
    vote.signature = chain._signer[departed](
        vote.sign_bytes(chain.gen.chain_id))
    blk.header.last_commit_hash = blk.last_commit.hash()
    wire[at] = encoding.cdumps(blk.to_obj())
    return at, wire
