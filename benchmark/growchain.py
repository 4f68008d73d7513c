"""A block chain whose validator set is still FILLING, made from --seed
and held as wire bytes: what a peer serves a full node that joins a
young proof-of-stake chain, or any chain below its validator cap.

benchmark/joinchain.py's chain (`JoinChain`, subclassed: its keys, its
stake law, its blocks made and applied by the program's serial executor,
its commits signed by every member of the set in force) with other
changes. The genesis carries `genesis_vals` validators, ranks 1 to
`genesis_vals` of `stake_scale // (r + 2)` dealt by a seeded shuffle.
At distinct seeded heights, none at height 1, a block carries ONE change
besides its key=value transactions, each as `val:<pubkey hex>/<power>`:

- a join: a key the chain has never seen enters with the law's power at
  the rank below everyone who entered before it (the k-th joiner at rank
  genesis_vals + k), nobody leaves, and the set GROWS by one;
- a leave: the member of least stake goes (power 0), nobody enters, and
  the set SHRINKS by one; a key that left does not return;
- a stake change, as JoinChain's.

So above a join or a leave a commit has another size than every set
below it. Joins and leaves are spread evenly over the chain, each at a
seeded height of its own stretch, so that a pass has nearly the same
signatures whatever the seed (`_place_changes`); the set never passes
`cap` on the way and ends at genesis_vals + joins - leaves.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmark.joinchain import STAKE, JoinChain, val_tx

JOIN, LEAVE = "join", "leave"


class GrowChain(JoinChain):
    """JoinChain's fields, and: `size_at[h]`, how many validators are in
    force at height h (so how many sign block h's commit), for h = 1 to
    n_blocks + 1; `left_at[h]`, the key that block h removes.
    `joined_at[h]` is (a member that stays, the joiner) for a join at h:
    departed_signs_for_joiner then puts that member's good signature
    into the joiner's first vote."""

    def __init__(self, seed: int, n_blocks: int, genesis_vals: int, cap: int,
                 joins: int, leaves: int, stake_changes: int, n_txs: int,
                 tx_bytes: int, key_space: int,
                 stake_scale: int = 1_000_000, **kw):
        if genesis_vals + joins - leaves > cap:
            raise ValueError(f"{genesis_vals} + {joins} - {leaves} "
                             f"validators pass the cap of {cap}")
        self._genesis_vals, self._cap = genesis_vals, cap
        self._leaves, self._scale = leaves, stake_scale
        self.left_at: Dict[int, bytes] = {}
        self.size_at: Dict[int, int] = {}
        # JoinChain makes one standby key per change of membership:
        # here, per join
        super().__init__(seed, n_blocks, genesis_vals, stake_changes, joins,
                         n_txs, tx_bytes, key_space, stake_scale=stake_scale,
                         **kw)

    def _place_changes(self, n_blocks: int, stake_changes: int,
                       joins: int) -> Dict[int, str]:
        """Which block carries what, at distinct seeded heights in
        2..n_blocks, so that every seed offers the same work in another
        order: the joins and leaves one to each of as many equal
        stretches of the chain, at a seeded height of its stretch, and
        the leaves one to each of as many equal runs of those changes,
        at a seeded place of its run (drawn again while the set would
        pass the cap); the stake changes anywhere else."""
        leaves, rng = self._leaves, self._rng
        n = joins + leaves
        edges = [2 + i * (n_blocks - 1) // n for i in range(n + 1)]
        resizing = [rng.randrange(lo, hi) for lo, hi in zip(edges, edges[1:])]
        runs = [i * n // leaves for i in range(leaves + 1)] if leaves else []
        while True:
            left = {rng.randrange(lo, hi) for lo, hi in zip(runs, runs[1:])}
            kinds = [LEAVE if i in left else JOIN for i in range(n)]
            size = peak = self._genesis_vals
            for kind in kinds:
                size += 1 if kind == JOIN else -1
                peak = max(peak, size)
            if peak <= self._cap:
                break
        at = dict(zip(resizing, kinds))
        at.update((h, STAKE) for h in rng.sample(
            sorted(set(range(2, n_blocks + 1)) - set(resizing)),
            stake_changes))
        return at

    def _val_txs(self, h: int) -> List[bytes]:
        kind = self.change_at.get(h)
        vals = self._state.validators.validators
        if kind == JOIN:
            new = self._standby.pop(0)
            rank = self._genesis_vals + len(self.joined_at) + 1
            self.joined_at[h] = (self._rng.choice(vals).pubkey, new)
            return [val_tx(new, self._scale // (rank + 2))]
        if kind == LEAVE:
            out = min(vals, key=lambda v: (v.voting_power, v.address))
            self.left_at[h] = out.pubkey
            return [val_tx(out.pubkey, 0)]
        return super()._val_txs(h)

    def _build(self, h: int, txs: List[bytes], cut: bool = False) -> int:
        signed = super()._build(h, txs, cut=cut)
        self.size_at[h] = signed    # the sentinel's height too
        return signed


# --------------------------------------------------------- tampered copies

def _with_commit_changed(chain: JoinChain, at: int, change) -> List[bytes]:
    """The chain's wire cut one block above `at`, so that its last
    block only lends its LastCommit, the commit FOR block `at`, after
    `change(votes, block id, chain id)` has been at that commit's votes
    (and the header's hash of the commit made to match, so that nothing
    but judging the commit can tell)."""
    from tendermint_tpu.types import encoding
    from tendermint_tpu.types.block import Block
    wire = list(chain.wire[:at + 1])
    blk = Block.from_bytes(wire[at])
    change(blk.last_commit.precommits, blk.last_commit.block_id,
           chain.gen.chain_id)
    blk.header.last_commit_hash = blk.last_commit.hash()
    wire[at] = encoding.cdumps(blk.to_obj())
    return wire


def leaver_still_in_commit(chain: GrowChain, leave_height: int
                           ) -> Tuple[int, List[bytes]]:
    """(the height tampered with, the wire cut above it): at the first
    height after the leave that block `leave_height` carries, the
    commit still has a slot for the key that left, where its address
    sorts, with that key's good signature for the block: the commit the
    set of one block ago would have signed. A node that judged it
    under that set, or by the keys alone, would take it."""
    from tendermint_tpu.types.keys import address_of
    from tendermint_tpu.types.vote import Vote, VoteType
    at = leave_height + 1
    leaver = chain.left_at[leave_height]
    address = address_of(leaver)

    def one_slot_more(votes, block_id, chain_id):
        slot = sum(1 for v in votes if v.validator_address < address)
        vote = Vote(address, slot, at, 0, at * 10 ** 9 + len(votes),
                    VoteType.PRECOMMIT, block_id)
        vote.signature = chain._signer[leaver](vote.sign_bytes(chain_id))
        votes.insert(slot, vote)
    return at, _with_commit_changed(chain, at, one_slot_more)


def address_rewritten(chain: JoinChain, at: int, slot: int, other: int
                      ) -> List[bytes]:
    """The wire cut above `at` with ONE vote of the commit for block
    `at`, the one in `slot`, claiming the address of the member in slot
    `other`. Sign-bytes hold no address and a commit is judged slot by
    slot, so this commit is as good as the chain's own: a node has to
    ACCEPT it, all of it."""
    def claim(votes, _block_id, _chain_id):
        votes[slot].validator_address = votes[other].validator_address
    return _with_commit_changed(chain, at, claim)
