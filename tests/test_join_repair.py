"""Repair at the join (BlockchainReactor._repair): the block that brings
a key into force has every lane collected for its address and not yet
applied verified under that key in ONE verifier call, and key and
verdict replace the lane's, so that the per-block judge
(ValidatorSet.check_commit_lanes) meets no lane under another key.

On the rehearsal-sized growing chain of the grow cell (6 validators
growing to 12, 48 blocks, 8 joins, 2 leaves, 10 changes of stake) at
windows of 4 and 8: one `sync.repair` a join and none for a leave or a
change of stake; every `sync.judge` with `again` 0; a repair's lanes
exactly the joiner's votes in the blocks collected and not applied, of
both windows; `tm_sync_lanes_total` each lane once; the verifier asked
for as many signatures as before; a window dropped with repairs pending
leaves nothing behind; the tampered copies of
benchmark/drivers/sync_grow.py judged as a node without repairs judges
them; what a repair does not foresee left to the judge."""

import pytest

from benchmark import joinref
from benchmark.chain import ChainBuilder, forge_precommit
from benchmark.drivers.sync import drive, fresh_reactor
from benchmark.drivers.sync_join import synced
from benchmark.growchain import (JOIN, LEAVE, GrowChain, address_rewritten,
                                 leaver_still_in_commit)
from benchmark.joinchain import STAKE, departed_signs_for_joiner
from benchmark.spans import SpanLog
from tendermint_tpu.blockchain.reactor import BlockchainReactor, _Window
from tendermint_tpu.models.verifier import default_verifier
from tendermint_tpu.types.block import Block
from tendermint_tpu.types.keys import address_of
from tendermint_tpu.types.validator_set import Validator, ValidatorSet

from test_fast_sync_churn import (PlacedGrowChain, counts, events, judged,
                                  recorder, repaired, serial, stopped_at)

N_BLOCKS = 48
__all__ = ["recorder"]          # the fixture, imported for this module


def rehearsal_chain(seed=7, **kw):
    """The grow cell's rehearsal (benchmark/configs/chain_grow_join.json
    `rehearsal`)."""
    return GrowChain(seed, N_BLOCKS, 6, 12, 8, 2, 10, 4, 64, 8, **kw)


def sync(chain, window, wire=None):
    return synced(chain.gen, default_verifier(), window,
                  chain.wire if wire is None else wire,
                  SpanLog(annotate=False))


def repair_counts(telemetry):
    return (int(telemetry.value("sync_repairs_total") or 0),
            int(telemetry.value("sync_repaired_lanes_total") or 0))


@pytest.fixture
def repairs_from_zero(recorder):
    from tendermint_tpu.blockchain import reactor
    children = [reactor._m_repairs._implicit,
                reactor._m_repaired_lanes._implicit]
    held = [c.value for c in children]
    for c in children:
        c.value = 0.0
    yield recorder
    for c, value in zip(children, held):
        c.value = value


@pytest.fixture
def without_repairs(monkeypatch):
    """Switches the repair off and on: off, the node is the parent
    commit's, whose judge alone meets the joiners' lanes."""
    repair = BlockchainReactor._repair

    def switch(on: bool):
        monkeypatch.setattr(BlockchainReactor, "_repair",
                            repair if on else (lambda self, *a: None))
    return switch


# ------------------------------------------------ the set's side of a join

def members(n):
    return [Validator(bytes([i]) * 32, 10 + i) for i in range(n)]


def test_joined_since_names_who_came_and_costs_a_copy_nothing():
    before = ValidatorSet(members(5))
    assert before.copy().joined_since(before) == []
    assert before.copy()._index is before._index
    # a change of stake and a leave bring no key
    moved = before.update_with_changes([Validator(bytes([1]) * 32, 99)])
    assert moved.joined_since(before) == []
    left = before.update_with_changes([Validator(bytes([2]) * 32, 0)])
    assert left.joined_since(before) == []
    # a join does, and a member replaced does
    joiner = Validator(bytes([77]) * 32, 5)
    grown = before.update_with_changes([joiner])
    assert [v.pubkey for v in grown.joined_since(before)] == [joiner.pubkey]
    replaced = before.update_with_changes(
        [Validator(bytes([2]) * 32, 0), joiner])
    assert [v.pubkey for v in replaced.joined_since(before)] == \
        [joiner.pubkey]
    assert len(replaced) == len(before)
    two = before.update_with_changes([joiner, Validator(bytes([78]) * 32, 6)])
    assert {v.pubkey for v in two.joined_since(before)} == {
        bytes([77]) * 32, bytes([78]) * 32}


@pytest.mark.parametrize("at", [5, 9, 12])
def test_rows_by_address_tells_the_strangers_and_their_lanes(at):
    """The collection's pairing hands over what it met: each address its
    set does not hold, with the lane that claims it; the rows are the
    ones it gives without being asked."""
    chain = PlacedGrowChain(23, {4: JOIN, 8: JOIN, 10: LEAVE})
    genesis = ValidatorSet([Validator(v.pubkey, v.power)
                            for v in chain.gen.validators])
    commit = Block.from_bytes(chain.wire[at]).last_commit
    strangers = {}
    rows = genesis.rows_by_address(commit, strangers)
    assert rows == genesis.rows_by_address(commit)
    present = [pc for pc in commit.precommits if pc is not None]
    # (the joiner of block 8 enters at the bottom, so it is the one
    # that leaves at 10)
    joiners = {address_of(chain.joined_at[h][1])
               for h in chain.joined_at if h < at} & {
                   pc.validator_address for pc in present}
    assert set(strangers) == joiners
    assert len(joiners) == {5: 1, 9: 2, 12: 1}[at]
    for address, lane in strangers.items():
        assert present[lane].validator_address == address
    # its own commit holds no stranger
    own = {}
    first = Block.from_bytes(chain.wire[2]).last_commit
    assert genesis.rows_by_address(first, own) == list(range(6))
    assert own == {}
    lanes, _for_block = genesis.commit_lanes_by_address(
        chain.gen.chain_id, None, at, commit, again := {})
    assert again == strangers and len(lanes) == len(present)


# ------------------------------------------------------ an honest chain

@pytest.fixture
def watched(monkeypatch):
    """[(height, want, got, heights, pending)] a repair: the triples
    the joiners' votes make in the blocks collected and not applied,
    the triples the verifier was handed, the heights of those blocks,
    and whether lanes of the window in flight were among them."""
    seen = []
    repair = BlockchainReactor._repair

    def watching(self, height, joined, window, after):
        chain_id = self.state.chain_id
        want, heights, pending = [], [], False
        for w, first in ((window, after), (self._pending_window, 0)):
            if w is None:
                continue
            for v in joined:
                for entry in w.per_block[first:]:
                    for pc in entry.commit.precommits:
                        if pc is not None and \
                                pc.validator_address == v.address:
                            want.append((v.pubkey, pc.sign_bytes(chain_id),
                                         pc.signature))
                            heights.append(entry.block.header.height)
                            pending |= w is not window
        verifier, got = self._verifier(), []
        inner = verifier.verify

        def verify(items):
            got.extend(items)
            return inner(items)
        verifier.verify = verify
        try:
            repair(self, height, joined, window, after)
        finally:
            del verifier.verify
        seen.append((height, want, got, heights, pending))
    monkeypatch.setattr(BlockchainReactor, "_repair", watching)
    return seen


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("seed", [7, 2**31 + 42])
def test_one_repair_a_join_and_the_judge_verifies_nothing_again(
        repairs_from_zero, watched, window, seed):
    recorder = repairs_from_zero
    chain = rehearsal_chain(seed)
    joins = sorted(chain.joined_at)
    assert len(joins) == 8 and len(chain.left_at) == 2
    verifier = default_verifier()
    sigs, calls = verifier.stats["sigs"], verifier.stats["calls"]
    reactor, error = sync(chain, window)
    assert error is None and reactor.state.last_block_height == N_BLOCKS
    sigs = verifier.stats["sigs"] - sigs
    calls = verifier.stats["calls"] - calls
    want, _sets, _apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    # one repair a join, none for a leave or a change of stake
    got = repaired(recorder)
    assert sorted(got) == joins == [h for h, *_ in watched]
    assert repair_counts(recorder) == (8, sum(got.values()))
    assert sum(got.values()) > 0
    # the judge, one event a block, met no lane under another key
    assert judged(recorder) == {h: 0 for h in range(1, N_BLOCKS + 1)}
    assert events(recorder, "sync.reverify") == []
    # a repair's lanes: the joiner's vote in every block collected and
    # not applied, from the block above the join to the last collected
    some_pending = False
    for (height, wanted, verified, heights, pending), event in zip(
            watched, events(recorder, "sync.repair")):
        assert verified == wanted
        assert heights == list(range(height + 1,
                                     height + 1 + len(heights)))
        assert event["req"] == height
        assert event["args"] == {"lanes": len(wanted),
                                 "blocks": len(heights)}
        some_pending |= pending
    assert some_pending
    # each lane counted once: used where its window's verdict stood,
    # discarded where a repair replaced it
    batched, again, used, lost = counts(recorder)
    sizes = sum(chain.size_at[h] for h in range(1, N_BLOCKS + 1))
    assert (batched, again) == (N_BLOCKS, 0)
    assert used + lost == sizes and lost == sum(got.values())
    # the same work as a judge alone had: every lost lane once more
    assert sigs == sizes + lost
    # in one call a join that found lanes, beside one a window
    windows = len(events(recorder, "sync.wait"))
    assert calls == windows + sum(
        1 for lanes in got.values() if lanes)


@pytest.mark.parametrize("window", [4, 8])
def test_the_work_is_the_judges_own_moved(repairs_from_zero,
                                          without_repairs, window):
    """With and without repairs: the same state, the same signatures
    asked of the verifier, the same lanes used and discarded; what the
    judge verified again block by block is what the repairs verify."""
    recorder = repairs_from_zero
    chain = rehearsal_chain(11)
    verifier = default_verifier()
    seen = {}
    for on in (False, True):
        without_repairs(on)
        recorder.TRACER.clear()
        before, sigs = counts(recorder), verifier.stats["sigs"]
        reactor, error = sync(chain, window)
        assert error is None and reactor.state.last_block_height == N_BLOCKS
        seen[on] = (reactor.state.to_obj(),
                    tuple(a - b for a, b in zip(counts(recorder), before)),
                    verifier.stats["sigs"] - sigs)
        again = sum(judged(recorder).values())
        lanes = sum(repaired(recorder).values())
        assert (again > 0, lanes) == (True, 0) if not on else \
            (again, lanes > 0) == (0, True)
        seen[on] += (again + lanes,)
    assert seen[True] == seen[False]


def test_a_set_that_holds_still_is_never_repaired(repairs_from_zero):
    recorder = repairs_from_zero
    builder = ChainBuilder(3, 5, 2, 48, 8)
    wire, _expect = builder.build_wire(12)
    wire += builder.build_wire(1, with_txs=False)[0]
    reactor = fresh_reactor(builder.gen, default_verifier(), 4)
    try:
        drive(reactor, wire, SpanLog(annotate=False))
    finally:
        reactor.stop()
    assert reactor.state.last_block_height == 12
    assert repair_counts(recorder) == (0, 0)
    assert events(recorder, "sync.repair") == []
    assert counts(recorder) == (12, 0, 60, 0)


def test_changes_of_stake_and_leaves_are_not_repaired(repairs_from_zero):
    recorder = repairs_from_zero
    chain = PlacedGrowChain(24, {3: STAKE, 5: LEAVE, 6: STAKE, 9: LEAVE})
    reactor, error = sync(chain, 4)
    assert error is None and reactor.state.last_block_height == 26
    assert repair_counts(recorder) == (0, 0)
    assert events(recorder, "sync.repair") == []
    assert counts(recorder)[3] == 0


def test_a_join_at_the_chains_last_block_repairs_no_lane(repairs_from_zero):
    recorder = repairs_from_zero
    chain = PlacedGrowChain(25, {26: JOIN})
    reactor, error = sync(chain, 4)
    assert error is None and reactor.state.last_block_height == 26
    assert repaired(recorder) == {26: 0}
    assert repair_counts(recorder) == (1, 0)


def test_a_key_that_does_not_fit_the_columns_is_left_to_the_judge(
        repairs_from_zero):
    """A window of Ed25519 columns and a joiner whose key has another
    width (no `val:` transaction brings one; another application may):
    the repair passes it by, and the lane keeps the key and the verdict
    it had, for the judge to find under another key."""
    import numpy as np
    from types import SimpleNamespace
    from tendermint_tpu.blockchain.reactor import _Collected
    from tendermint_tpu.types.keys import Secp256k1PrivKey
    from tendermint_tpu.types.sigcolumns import SigColumns
    recorder = repairs_from_zero
    chain = PlacedGrowChain(26, {4: JOIN})
    reactor = fresh_reactor(chain.gen, default_verifier(), 4)
    wide = Validator(Secp256k1PrivKey.generate(b"w" * 32).pubkey.secp256k1, 5)
    fits = Validator(chain.joined_at[4][1], 5)
    assert (len(wide.pubkey), len(fits.pubkey)) == (33, 32)

    def window():
        block = SimpleNamespace(header=SimpleNamespace(height=5))
        entry = _Collected(block, None, None, None, np.ones(2, np.bool_),
                           0, 2, True, {wide.address: 0, fits.address: 1})
        items = SigColumns(np.zeros((2, 32), np.uint8), [b"s" * 64] * 2,
                           [b"m"], np.zeros(2, np.int32))
        w = _Window([entry], items, b"", 1, None)
        w.ok = np.ones(2, np.bool_)
        return w
    try:
        w = window()
        reactor._repair(4, [wide], w, 0)
        assert not w.items.pk.any() and w.ok.all() and w.mended == {}
        assert w.per_block[0].strangers == {wide.address: 0,
                                            fits.address: 1}
        assert repair_counts(recorder) == (1, 0)
        reactor._repair(4, [wide, fits], w, 0)
        assert w.items.pk[1].tobytes() == fits.pubkey
        assert not w.items.pk[0].any()
        assert w.ok.tolist() == [True, False] and w.mended == {0: 1}
        assert w.per_block[0].strangers == {wide.address: 0}
        assert repair_counts(recorder) == (2, 1)
        # the blocks below `after` are applied: theirs is not to repair
        w = window()
        reactor._repair(4, [fits], w, 1)
        assert not w.items.pk.any() and w.ok.all() and w.mended == {}
        assert [e["args"] for e in events(recorder, "sync.repair")] == [
            {"lanes": 0, "blocks": 0}, {"lanes": 1, "blocks": 1},
            {"lanes": 0, "blocks": 0}]
    finally:
        reactor.stop()


# ------------------------------------------------------- tampered chains

def joiners_slot(chain, at, join):
    """Where the joiner of block `join` votes in the commit for `at`."""
    address = address_of(chain.joined_at[join][1])
    slot, = [i for i, pc in enumerate(
        Block.from_bytes(chain.wire[at]).last_commit.precommits)
        if pc.validator_address == address]
    return slot


def tampered_copies(chain):
    """benchmark/drivers/sync_grow.py's five, and the forged precommit
    twice: in a founder's lane and in a lane a repair has verified.
    name -> (the height judged, wire, refused there or accepted)."""
    joins, leaves = sorted(chain.joined_at), sorted(chain.left_at)
    forged_at = joins[1] + 2
    founder = address_of(chain.gen.validators[0].pubkey)
    founders_slot, = [i for i, pc in enumerate(
        Block.from_bytes(chain.wire[forged_at]).last_commit.precommits)
        if pc.validator_address == founder]
    copies = {}
    for name, slot in (("forged_precommit_of_a_founder", founders_slot),
                       ("forged_precommit_of_a_joiner",
                        joiners_slot(chain, forged_at, joins[1]))):
        wire = list(chain.wire)
        wire[forged_at] = forge_precommit(wire[forged_at], slot)
        copies[name] = (forged_at, wire, True)
    at, wire = departed_signs_for_joiner(chain, joins[0])
    copies["joiners_vote_signed_by_another_member"] = (at, wire, True)
    at, wire = leaver_still_in_commit(chain, leaves[0])
    copies["leavers_slot_still_in_the_commit"] = (at, wire, True)
    copies["join_val_tx_cut"] = (
        joins[2] + 1, rehearsal_chain(cut_val_at=joins[2]).wire, True)
    at = joins[3] + 1
    copies["vote_claims_another_members_address"] = (
        at, address_rewritten(chain, at, 0, 3), False)
    copies["vote_claims_a_joiners_address"] = (
        at, address_rewritten(chain, at, 0, joiners_slot(chain, at, joins[3])),
        False)
    return copies


CASES = ("forged_precommit_of_a_founder", "forged_precommit_of_a_joiner",
         "joiners_vote_signed_by_another_member",
         "leavers_slot_still_in_the_commit", "join_val_tx_cut",
         "vote_claims_another_members_address",
         "vote_claims_a_joiners_address")


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("case", CASES)
def test_a_tampered_copy_is_judged_as_a_node_without_repairs_judges_it(
        repairs_from_zero, without_repairs, case, window):
    chain = rehearsal_chain()
    at, wire, refused = tampered_copies(chain)[case]
    said = {}
    for on in (False, True):
        without_repairs(on)
        reactor, error = sync(chain, window, wire)
        said[on] = (stopped_at(reactor), type(error),
                    reactor.block_store.height())
    assert said[True] == said[False]
    (reached, punished), raised, _stored = said[True]
    ref = joinref.replay(chain.genesis_wire, wire)
    if refused:
        # by the judge, at the peer's cost (the copy with its `val:`
        # cut too: the commit above has a slot more than the set the
        # node arrived at)
        assert reached == ref.height == at - 1 and ref.refused_at == at
        assert punished and raised is type(None)
    else:
        assert reached == ref.height == at and ref.refused_at is None
        assert not punished and raised is type(None)
    assert repair_counts(repairs_from_zero)[0] > 0


def test_a_repaired_lane_with_a_bad_signature_is_refused_at_its_block(
        repairs_from_zero, watched):
    """The repair verifies the forged lane, carries False to its block
    and raises nothing itself: the blocks below it apply, the judge
    refuses the block, verifying nothing again."""
    recorder = repairs_from_zero
    chain = rehearsal_chain()
    at, wire, _ = tampered_copies(chain)["forged_precommit_of_a_joiner"]
    reactor, error = sync(chain, 8, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    assert set(judged(recorder).values()) == {0}
    assert max(judged(recorder)) == at - 1      # the last that passed
    forged = Block.from_bytes(wire[at]).last_commit.precommits[
        joiners_slot(chain, at, sorted(chain.joined_at)[1])].signature
    assert any(forged in {sig for _k, _m, sig in got}
               for _h, _want, got, _heights, _p in watched)


# ------------------------------------------------------ a window dropped

def test_a_window_dropped_with_repairs_pending_leaves_none_behind(
        repairs_from_zero, monkeypatch):
    """A join, then a forged commit further up the same window: the
    window in flight holds the join's repairs when it is dropped. The
    node goes on with an honest peer and ends where a serial node ends,
    every lane of every applied block counted once."""
    recorder = repairs_from_zero
    chain = PlacedGrowChain(27, {5: JOIN, 12: STAKE, 17: JOIN})
    forged_at = 7
    wire = list(chain.wire)
    wire[forged_at] = forge_precommit(wire[forged_at], 1)
    waiting = []
    lay = _Window.lay

    def laying(self, lanes, key, verdicts):
        lay(self, lanes, key, verdicts)
        waiting.append((self, len(self.repairs)))
    monkeypatch.setattr(_Window, "lay", laying)
    reactor = fresh_reactor(chain.gen, default_verifier(), 4)
    try:
        drive(reactor, wire, SpanLog(annotate=False))
        assert stopped_at(reactor) == (forged_at - 1, True)
        # the join at 5 repaired the window in flight, and it is gone
        dropped = [w for w, held in waiting if held]
        assert dropped and reactor._pending_window is None
        assert set(repaired(recorder)) == {5}
        assert repaired(recorder)[5] > forged_at - 5
        reactor.switch.stopped.clear()
        drive(reactor, chain.wire, SpanLog(annotate=False))
    finally:
        reactor.stop()
    assert reactor.state.last_block_height == 26
    assert reactor.switch.stopped == []
    want, _sets, _apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    # only the dropped window still holds what waited for its verdicts
    assert [w for w, _held in waiting if w.repairs] == dropped[:1]
    batched, again, used, lost = counts(recorder)
    assert (batched, again) == (26, 0)
    assert used + lost == sum(chain.size_at[h] for h in range(1, 27))
    # blocks 6 lost the joiner's lane to the repair; 7 and up were
    # collected again under a set that holds the joiner
    assert set(judged(recorder).values()) == {0}
    assert sorted(repaired(recorder)) == [5, 17]
