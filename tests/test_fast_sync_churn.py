"""Fast-sync over a chain whose validator set moves through `val:`
transactions (EndBlock): BlockchainReactor's window engine against
`apply_block` one block at a time and against benchmark/joinref.py's
plain replay, at windows of 4 and 8, with changes placed at a window's
edges, in consecutive blocks and as a join and a leave in one block;
three tampered chains, each stopping at its own height; and the spans
and counters that tell a batched block from a re-verified one."""

import json

import pytest

from benchmark import joinref
from benchmark.chain import ChainBuilder, forge_precommit
from benchmark.drivers.sync import PEER_ID, drive, fresh_reactor
from benchmark.drivers.sync_join import synced
from benchmark.growchain import (JOIN, LEAVE, GrowChain, address_rewritten,
                                 leaver_still_in_commit)
from benchmark.joinchain import (MEMBERSHIP, STAKE, JoinChain,
                                 departed_signs_for_joiner)
from benchmark.spans import SpanLog
from tendermint_tpu.models.verifier import default_verifier

N_VALS, N_BLOCKS = 6, 26

# a window of w blocks peeked pools w - 1 commits, so window k covers
# blocks (k - 1)(w - 1) + 1 .. k(w - 1)
PLACED = {
    "first_block_of_a_window_4": (4, {4: STAKE, 7: STAKE}),
    "last_block_of_a_window_4": (4, {3: STAKE, 6: STAKE, 9: MEMBERSHIP}),
    "first_block_of_a_window_8": (8, {8: STAKE, 15: MEMBERSHIP}),
    "last_block_of_a_window_8": (8, {7: STAKE, 14: STAKE}),
    "consecutive_blocks": (4, {5: STAKE, 6: STAKE, 7: MEMBERSHIP,
                               8: STAKE, 9: MEMBERSHIP}),
    "join_and_leave_in_one_block": (8, {2: MEMBERSHIP}),
    "the_last_block": (4, {N_BLOCKS: MEMBERSHIP}),
    "no_change": (4, {}),
}


class PlacedChain(JoinChain):
    """JoinChain with its changes where a test puts them."""

    def __init__(self, seed, placed, n_blocks=N_BLOCKS, n_vals=N_VALS, **kw):
        self._placed = dict(placed)
        kinds = list(placed.values())
        super().__init__(seed, n_blocks, n_vals, kinds.count(STAKE),
                         kinds.count(MEMBERSHIP), n_txs=3, tx_bytes=48,
                         key_space=8, **kw)

    def _place_changes(self, n_blocks, stake_changes, membership_changes):
        return self._placed


def sync(chain, window, wire=None):
    return synced(chain.gen, default_verifier(), window,
                  chain.wire if wire is None else wire,
                  SpanLog(annotate=False))


def serial(chain):
    """The chain applied one block at a time: each commit judged by
    `verify_commit` under the state's set, then `apply_block`. Returns
    (final state, [validators hash in force at height 1, 2, ...],
    [app hash after block 1, 2, ...])."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.abci.types import ValidatorUpdate
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.storage import MemDB, StateStore
    from tendermint_tpu.types.block import Block, BlockID
    store = StateStore(MemDB())
    state = store.load_or_genesis(chain.gen)
    conns = AppConns(local_client_creator(KVStoreApp()))
    conns.consensus.init_chain(
        [ValidatorUpdate(v.pubkey, v.voting_power)
         for v in state.validators.validators], chain.gen.chain_id)
    exec_ = BlockExecutor(store, conns.consensus)
    blocks = [Block.from_bytes(raw) for raw in chain.wire]
    sets, apps = [state.validators.hash()], []
    for block, above in zip(blocks, blocks[1:]):
        parts = block.make_part_set(
            state.consensus_params.block_gossip.block_part_size_bytes)
        block_id = BlockID(block.hash(), parts.header())
        state.validators.verify_commit(
            state.chain_id, block_id, block.header.height, above.last_commit)
        state = exec_.apply_block(state, block_id, block,
                                  trust_last_commit=True)
        sets.append(state.validators.hash())
        apps.append(state.app_hash)
    return state, sets, apps


@pytest.mark.parametrize("case", sorted(PLACED))
def test_the_window_engine_equals_one_block_at_a_time(case):
    window, placed = PLACED[case]
    chain = PlacedChain(11, placed)
    assert chain.change_at == placed
    reactor, error = sync(chain, window)
    assert error is None and reactor.switch.stopped == []
    state = reactor.state
    assert state.last_block_height == N_BLOCKS

    want, sets, apps = serial(chain)
    assert state.to_obj() == want.to_obj()
    assert state.validators.hash() == sets[-1]
    assert state.last_height_validators_changed == (
        max(placed) + 1 if placed else 1)
    metas = [reactor.block_store.load_block_meta(h)
             for h in range(1, N_BLOCKS + 1)]
    assert [m.header.validators_hash for m in metas] == sets[:-1]
    assert [m.header.app_hash for m in metas[1:]] + [state.app_hash] == apps
    assert [(m.block_id.hash, m.header.app_hash) for m in metas] == \
        chain.expect[:N_BLOCKS]
    # the set a height was judged under is the one the state store kept
    store = reactor.block_exec.state_store
    assert [store.load_validators(h).hash()
            for h in range(1, N_BLOCKS + 2)] == sets
    assert len(set(sets)) == 1 + len(placed)

    ref = joinref.replay(chain.genesis_wire, chain.wire)
    assert (ref.height, ref.refused_at) == (N_BLOCKS, None)
    assert ref.validators_hashes == sets and ref.app_hashes == apps
    assert ref.validators == [(v.pubkey, v.voting_power)
                              for v in state.validators.validators]


@pytest.mark.parametrize("window", [4, 8])
def test_a_seeded_chain_of_the_rehearsals_size(window):
    chain = JoinChain(5, 48, 8, 12, 4, 4, 64, 8)
    kinds = list(chain.change_at.values())
    assert (kinds.count(STAKE), kinds.count(MEMBERSHIP)) == (12, 4)
    assert min(chain.change_at) >= 2 and len(chain.joined_at) == 4
    assert chain.n_sigs == 48 * 8 and len(chain.wire) == 49
    reactor, error = sync(chain, window)
    assert error is None and reactor.state.last_block_height == 48
    ref = joinref.replay(chain.genesis_wire, chain.wire)
    assert ref.height == 48
    assert reactor.state.validators.hash() == ref.validators_hashes[-1]
    assert reactor.state.app_hash == ref.app_hashes[-1]
    assert len(set(ref.validators_hashes)) == 17


# ------------------------------------------------------- tampered chains

CHURN = {3: STAKE, 6: MEMBERSHIP, 10: STAKE, 13: MEMBERSHIP, 14: STAKE}


def stopped_at(reactor):
    punished = {peer for peer, _why in reactor.switch.stopped} == {PEER_ID} \
        and PEER_ID not in reactor.pool.peers
    return reactor.state.last_block_height, punished


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("at", [4, 7, 11, 15, 20])
def test_a_forged_precommit_stops_the_sync_below_it(window, at):
    """Also right after a change of set (7, 11, 15), where the block is
    judged by the synchronous re-verify and not by the window's batch."""
    chain = PlacedChain(12, CHURN)
    wire = list(chain.wire)
    wire[at] = forge_precommit(wire[at], at % N_VALS)
    reactor, error = sync(chain, window, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    assert reactor.block_store.height() == at - 1
    ref = joinref.replay(chain.genesis_wire, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        at - 1, at, joinref.SIGNATURE)


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("change", [6, 13])
def test_the_departed_keys_signature_for_the_joiner_is_refused(window,
                                                               change):
    chain = PlacedChain(13, CHURN)
    at, wire = departed_signs_for_joiner(chain, change)
    assert at == change + 1 and len(wire) == at + 1
    # the signature is a good one, by a key that sat in the set a block ago
    departed, joiner = chain.joined_at[change]
    assert departed != joiner
    reactor, error = sync(chain, window, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    ref = joinref.replay(chain.genesis_wire, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        at - 1, at, joinref.SIGNATURE)
    # the untampered prefix syncs through the same height
    reactor, error = sync(chain, window, chain.wire[:at + 1])
    assert error is None and stopped_at(reactor) == (at, False)


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("cut", [3, 10, 14])
def test_a_block_with_its_val_transaction_cut_is_refused_one_height_up(
        window, cut):
    from tendermint_tpu.state.validation import BlockValidationError
    chain = PlacedChain(14, CHURN)
    copy = PlacedChain(14, CHURN, cut_val_at=cut)
    assert copy.wire[:cut - 1] == chain.wire[:cut - 1]
    assert copy.wire[cut - 1] != chain.wire[cut - 1]
    assert len(copy.wire) == cut + 2
    assert not any(tx.startswith(b"val:") for tx in joinref.txs_of(
        json.loads(copy.wire[cut - 1])))
    reactor, error = sync(copy, window, copy.wire)
    assert isinstance(error, BlockValidationError)
    assert "validators_hash" in str(error)
    assert stopped_at(reactor) == (cut, False)
    ref = joinref.replay(copy.genesis_wire, copy.wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        cut, cut + 1, joinref.VALIDATORS_HASH)


# ---------------------------------------------------- spans and counters

@pytest.fixture
def recorder():
    """Telemetry on, the ring and the window engine's counters from
    zero; as found afterwards."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain import reactor
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.TRACER.clear()
    children = [fam.labels(how)
                for fam, hows in ((reactor._m_commits,
                                   ("batched", "reverified")),
                                  (reactor._m_lanes, ("used", "discarded")))
                for how in hows] + [reactor._m_live_judged._implicit,
                                    reactor._m_resized._implicit]
    held = [c.value for c in children]
    for c in children:
        c.value = 0.0
    yield telemetry
    for c, value in zip(children, held):
        c.value = value
    telemetry.set_enabled(was)


def counts(telemetry):
    return tuple(int(telemetry.value(family, {"how": how}) or 0)
                 for family, how in (("sync_commits_total", "batched"),
                                     ("sync_commits_total", "reverified"),
                                     ("sync_lanes_total", "used"),
                                     ("sync_lanes_total", "discarded")))


def live_judged(telemetry):
    return int(telemetry.value("sync_live_judged_total") or 0)


def events(telemetry, name):
    return [e for e in telemetry.TRACER.events() if e["name"] == name]


def test_a_constant_set_is_batched_and_nothing_is_reverified(recorder):
    builder = ChainBuilder(3, 5, 2, 48, 8)
    wire, _expect = builder.build_wire(12)
    wire += builder.build_wire(1, with_txs=False)[0]
    reactor = fresh_reactor(builder.gen, default_verifier(), 4)
    try:
        drive(reactor, wire, SpanLog(annotate=False))
    finally:
        reactor.stop()
    assert reactor.state.last_block_height == 12
    assert counts(recorder) == (12, 0, 60, 0)
    assert live_judged(recorder) == 0
    assert events(recorder, "sync.reverify") == []
    updates = events(recorder, "apply.update")
    assert [e["req"] for e in updates] == list(range(1, 13))
    assert {e["args"]["changed"] for e in updates} == {0}


@pytest.fixture
def collected_under(monkeypatch):
    """{height: the set its window was collected under}, filled as a
    sync collects."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    under, collect = {}, BlockchainReactor._collect_window

    def recording(self, skip):
        out = collect(self, skip)
        for entry in (out[0] if out else ()):
            under[entry[0].header.height] = self.state.validators
        return out
    monkeypatch.setattr(BlockchainReactor, "_collect_window", recording)
    return under


@pytest.mark.parametrize("window", [4, 8])
def test_blocks_above_a_change_are_reverified_and_counted(
        recorder, collected_under, window):
    """Nothing above a change is verified whole again: a change of stake
    discards no lane, a join the joiner's alone, and the joiner's
    signature is what is verified again."""
    verifier = default_verifier()

    def synced_and_counted(placed):
        collected_under.clear()
        recorder.TRACER.clear()
        chain = PlacedChain(15, placed)
        sigs, live = verifier.stats["sigs"], live_judged(recorder)
        before = counts(recorder)
        reactor, error = sync(chain, window)
        assert error is None and reactor.state.last_block_height == N_BLOCKS
        assert events(recorder, "sync.reverify") == []
        assert events(recorder, "commit.collect") == []
        sigs = verifier.stats["sigs"] - sigs
        _state, sets, _apps = serial(chain)
        stale = [h for h in range(1, N_BLOCKS + 1)
                 if collected_under[h].hash() != sets[h - 1]]
        return (chain, reactor, stale,
                tuple(a - b for a, b in zip(counts(recorder), before)),
                sigs, live_judged(recorder) - live)

    # above a change of stake: every window up to two windows above it
    # was collected under the set before, and every lane's key stands
    chain, _reactor, stale, got, sigs, live = synced_and_counted({5: STAKE})
    assert got == (N_BLOCKS, 0, N_BLOCKS * N_VALS, 0)
    assert sigs == N_BLOCKS * N_VALS
    assert live == len(stale) >= window - 1 and min(stale) == 6

    # above a join as well: the collection set has never seen the joiner
    chain, reactor, stale, got, sigs, live = synced_and_counted(
        {5: STAKE, 12: MEMBERSHIP})
    (_departed, joiner), = chain.joined_at.values()
    unseen = [h for h in stale if h >= 13 and joiner not in
              {v.pubkey for v in collected_under[h].validators}]
    assert len(unseen) >= min(window - 1, N_BLOCKS - 12)
    assert unseen[0] == 13
    batched, again, used, lost = got
    assert (batched, again) == (N_BLOCKS, 0)
    assert lost == len(unseen) and used == N_BLOCKS * N_VALS - lost
    assert sigs == N_BLOCKS * N_VALS + lost
    assert live == len(stale) > len(unseen) and min(stale) == 6
    changed = {e["req"]: e["args"]["changed"]
               for e in events(recorder, "apply.update")}
    assert changed == {h: {5: 1, 12: 2}.get(h, 0)
                       for h in range(1, N_BLOCKS + 1)}


class PlacedGrowChain(GrowChain):
    """GrowChain (joins that grow the set, leaves that shrink it) with
    its changes where a test puts them."""

    def __init__(self, seed, placed, n_blocks=N_BLOCKS, n_vals=N_VALS, **kw):
        self._placed = dict(placed)
        kinds = list(placed.values())
        super().__init__(seed, n_blocks, n_vals, n_vals + kinds.count(JOIN),
                         kinds.count(JOIN), kinds.count(LEAVE),
                         kinds.count(STAKE), n_txs=3, tx_bytes=48,
                         key_space=8, **kw)

    def _place_changes(self, n_blocks, stake_changes, joins):
        return self._placed


def resized_blocks(telemetry):
    return int(telemetry.value("sync_resized_total") or 0)


def judged(telemetry):
    """{height: lanes verified again} of the `sync.judge` events."""
    return {e["req"]: e["args"]["again"]
            for e in events(telemetry, "sync.judge")}


def repaired(telemetry):
    """{the height that brought a key: lanes verified for it} of the
    `sync.repair` events."""
    return {e["req"]: e["args"]["lanes"]
            for e in events(telemetry, "sync.repair")}


def test_a_commit_of_another_size_than_the_windows_set_comes_with_lanes(
        recorder, collected_under):
    """A set that grows: the commits above the join have one vote more
    than the set their window was collected under, and come through the
    window's pooled batch all the same, paired by address; the joiner's
    lane alone is verified again."""
    n_blocks, grows_at = 14, 4
    chain = PlacedGrowChain(17, {grows_at: JOIN, 9: STAKE},
                            n_blocks=n_blocks)
    sizes = [chain.size_at[h] for h in range(1, n_blocks + 1)]
    assert sizes == [N_VALS] * grows_at + [N_VALS + 1] * (n_blocks - grows_at)
    verifier = default_verifier()
    sigs = verifier.stats["sigs"]
    reactor, error = sync(chain, 4)
    assert error is None and reactor.state.last_block_height == n_blocks
    assert len(reactor.state.validators) == N_VALS + 1
    # no block is verified whole again
    batched, again, used, lost = counts(recorder)
    assert (batched, again) == (n_blocks, 0)
    assert events(recorder, "sync.reverify") == []
    assert events(recorder, "commit.collect") == []
    sigs = verifier.stats["sigs"] - sigs
    want, _sets, _apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    # the blocks above the join whose window had not seen it
    joiner = chain.joined_at[grows_at][1]
    other_size = [h for h in range(1, n_blocks + 1)
                  if len(collected_under[h]) != chain.size_at[h]]
    assert other_size and other_size[0] == grows_at + 1
    assert len(other_size) >= 3
    assert resized_blocks(recorder) == len(other_size)
    assert all(joiner not in {v.pubkey for v in collected_under[h].validators}
               for h in other_size)
    # every vote brought a lane, and the joiner's alone was lost
    assert used + lost == sum(sizes) and lost == len(other_size)
    assert sigs == sum(sizes) + lost
    # the live judge, one event a block, and nothing left for it to
    # verify again: the join's one repair took the joiner's lanes
    assert judged(recorder) == {h: 0 for h in range(1, n_blocks + 1)}
    assert repaired(recorder) == {grows_at: len(other_size)}

    # a forged signature there is refused at its height
    at = other_size[1]
    wire = list(chain.wire)
    wire[at] = forge_precommit(wire[at], 2)
    reactor, error = sync(chain, 4, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    # as is the joiner's vote signed by a member of the set below
    at, wire = departed_signs_for_joiner(chain, grows_at)
    reactor, error = sync(chain, 4, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    assert events(recorder, "sync.reverify") == []


RESIZING = {
    "a_set_that_shrinks": (4, {5: LEAVE, 9: STAKE}),
    "a_join_and_a_leave_inside_one_window": (8, {3: JOIN, 5: LEAVE}),
    "grown_then_shrunk_past_its_windows": (
        4, {2: JOIN, 3: JOIN, 4: JOIN, 13: LEAVE, 14: LEAVE, 15: LEAVE}),
    "consecutive_joins_at_a_windows_edge": (4, {4: JOIN, 5: JOIN, 8: JOIN}),
    "a_leave_below_the_last_block": (4, {N_BLOCKS - 1: LEAVE}),
}


@pytest.mark.parametrize("case", sorted(RESIZING))
def test_a_set_that_changes_size_stays_on_the_pooled_path(
        recorder, collected_under, case):
    window, placed = RESIZING[case]
    chain = PlacedGrowChain(18, placed)
    reactor, error = sync(chain, window)
    assert error is None and reactor.switch.stopped == []
    assert reactor.state.last_block_height == N_BLOCKS
    want, sets, apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    ref = joinref.replay(chain.genesis_wire, chain.wire)
    assert (ref.height, ref.refused_at) == (N_BLOCKS, None)
    assert ref.validators_hashes == sets and ref.app_hashes == apps
    heights = range(1, N_BLOCKS + 1)
    assert [len(collected_under[h]) for h in heights] != \
        [chain.size_at[h] for h in heights]
    smaller = [h for h in heights
               if len(collected_under[h]) < chain.size_at[h]]
    larger = [h for h in heights
              if len(collected_under[h]) > chain.size_at[h]]
    batched, again, used, lost = counts(recorder)
    assert (batched, again) == (N_BLOCKS, 0)
    assert events(recorder, "sync.reverify") == []
    assert resized_blocks(recorder) == len(smaller) + len(larger)
    assert used + lost == sum(chain.size_at[h] for h in heights)
    # a lane is lost where the live set holds a key its window's set
    # did not: the joiners', never a leaver's
    unseen = sum(
        len({v.pubkey for v in store_set(reactor, h).validators}
            - {v.pubkey for v in collected_under[h].validators})
        for h in heights)
    assert lost == unseen == sum(repaired(recorder).values())
    assert set(repaired(recorder)) == {
        h for h, kind in placed.items() if kind == JOIN}
    assert set(judged(recorder).values()) == {0}
    if case == "a_set_that_shrinks":
        assert larger and not smaller and lost == 0
    if case == "grown_then_shrunk_past_its_windows":
        # a window collected under a set smaller than the commit, and
        # one under a larger
        assert smaller and larger
    if case == "a_join_and_a_leave_inside_one_window":
        # the joiner enters at the bottom, so it is the one that leaves:
        # above the leave the set is the genesis set again
        assert smaller == [4, 5] and not larger and lost == 2


class SecpSigner:
    """A secp256k1 key in the shape of `kvref.openssl_signer`'s keys,
    for the chain builders: `.sign`, `.public_key().public_bytes_raw()`."""

    def __init__(self, seed):
        from tendermint_tpu.types.keys import Secp256k1PrivKey
        self.key = Secp256k1PrivKey.generate(seed)
        self.sign = self.key.sign

    def public_key(self):
        return self

    def public_bytes_raw(self):
        return self.key.pubkey.secp256k1


def store_set(reactor, height):
    return reactor.block_exec.state_store.load_validators(height)


def test_a_leavers_slot_still_in_the_commit_is_refused_at_its_height(
        recorder):
    chain = PlacedGrowChain(19, {3: JOIN, 6: LEAVE, 9: STAKE})
    at, wire = leaver_still_in_commit(chain, 6)
    assert at == 7 and len(wire) == 8
    reactor, error = sync(chain, 4, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    assert events(recorder, "sync.reverify") == []
    ref = joinref.replay(chain.genesis_wire, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        at - 1, at, joinref.COMMIT)


@pytest.mark.parametrize("at", [5, 8, 11])
def test_a_wrong_validator_address_costs_one_lane_and_no_verdict(
        recorder, at):
    """Above a change of size a vote is paired with the key its address
    names. A vote that names another member's address is verified under
    the wrong key, its lane is lost, and the live judge verifies it
    again under the key of its slot: the commit is as good as before."""
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.keys import address_of
    chain = PlacedGrowChain(20, {4: JOIN, 9: JOIN})
    # two members every collection set has seen
    founders = {address_of(v.pubkey) for v in chain.gen.validators}
    slot, other = [i for i, v in enumerate(
        Block.from_bytes(chain.wire[at]).last_commit.precommits)
        if v.validator_address in founders][:2]
    claims = address_rewritten(chain, at, slot, other)
    lost = {}
    for name, wire in (("honest", chain.wire[:at + 1]), ("claims", claims)):
        before = counts(recorder)
        reactor, error = sync(chain, 4, wire)
        assert error is None and stopped_at(reactor) == (at, False)
        lost[name] = counts(recorder)[3] - before[3]
    assert lost["claims"] == lost["honest"] + 1
    assert joinref.replay(chain.genesis_wire, claims).height == at


def test_a_secp256k1_member_keeps_the_set_on_the_pooled_path(
        recorder, monkeypatch):
    """A set with a key that is not Ed25519 travels as triples: paired
    by address like columns, judged by the same judge."""
    from benchmark import joinchain
    made, openssl = [], joinchain.openssl_signer

    def signer(seed):
        made.append(seed)
        return SecpSigner(seed) if len(made) == 2 else openssl(seed)
    monkeypatch.setattr(joinchain, "openssl_signer", signer)
    chain = PlacedGrowChain(21, {4: JOIN, 9: LEAVE, 12: STAKE, 15: JOIN})
    assert sorted(len(v.pubkey) for v in chain.gen.validators) == \
        [32] * (N_VALS - 1) + [33]
    reactor, error = sync(chain, 4)
    assert error is None and reactor.state.last_block_height == N_BLOCKS
    want, _sets, _apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    batched, again, used, lost = counts(recorder)
    assert (batched, again) == (N_BLOCKS, 0) and lost > 0
    assert used + lost == sum(chain.size_at[h]
                              for h in range(1, N_BLOCKS + 1))
    assert resized_blocks(recorder) > 0
    assert events(recorder, "sync.reverify") == []
    at = 6
    wire = list(chain.wire)
    wire[at] = forge_precommit(wire[at], 2)
    reactor, error = sync(chain, 4, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)


def test_a_commit_no_set_would_take_is_heard_from_verify_commit(recorder):
    """What is left of the `sync.reverify` branch: a vote that is no
    precommit of its commit's round brings no lanes, and the block is
    refused by verify_commit under the live set."""
    from tendermint_tpu.types import encoding
    from tendermint_tpu.types.block import Block
    chain = PlacedGrowChain(22, {3: JOIN})
    at = 6
    wire = list(chain.wire[:at + 2])
    blk = Block.from_bytes(wire[at])
    blk.last_commit.precommits[2].round = 1
    blk.header.last_commit_hash = blk.last_commit.hash()
    wire[at] = encoding.cdumps(blk.to_obj())
    reactor, error = sync(chain, 4, wire)
    assert error is None and stopped_at(reactor) == (at - 1, True)
    assert [e["req"] for e in events(recorder, "sync.reverify")] == [at]
    assert counts(recorder)[:2] == (at - 1, 0)


def copy_header_names_another_set(copy, height, reactor):
    from tendermint_tpu.types.block import Block
    header = Block.from_bytes(copy.wire[height - 1]).header
    return header.validators_hash != reactor.state.validators.hash()


@pytest.mark.parametrize("window", [4, 8])
def test_the_val_transaction_cut_at_the_first_change_is_refused_one_up(
        recorder, window):
    """Live set = collection set, header names another: the commit
    passes the live judge (every signature good, the node's stake on
    the block) and validate_block refuses the header; nobody is
    punished."""
    from tendermint_tpu.state.validation import BlockValidationError
    cut = min(CHURN)
    copy = PlacedChain(14, CHURN, cut_val_at=cut)
    reactor, error = sync(copy, window, copy.wire)
    assert isinstance(error, BlockValidationError)
    assert "validators_hash" in str(error)
    assert stopped_at(reactor) == (cut, False)
    # the node still holds the genesis set, under which every window was
    # collected: the commit above the cut came through the pooled lanes
    # (paired by address, since its header names another set) and not
    # through a whole verify
    assert reactor.state.last_height_validators_changed == 1
    assert copy_header_names_another_set(copy, cut + 1, reactor)
    assert counts(recorder) == (cut, 0, cut * N_VALS, 0)
    assert live_judged(recorder) == 0
    assert events(recorder, "sync.reverify") == []
    ref = joinref.replay(copy.genesis_wire, copy.wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        cut, cut + 1, joinref.VALIDATORS_HASH)


def test_with_telemetry_off_nothing_is_recorded():
    from tendermint_tpu import telemetry
    was = telemetry.enabled()
    telemetry.set_enabled(False)
    try:
        telemetry.TRACER.clear()
        before = counts(telemetry)
        chain = PlacedChain(16, {4: STAKE})
        reactor, error = sync(chain, 4)
        assert error is None and reactor.state.last_block_height == N_BLOCKS
        assert counts(telemetry) == before
        assert telemetry.TRACER.events() == []
    finally:
        telemetry.set_enabled(was)
