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
                for how in hows]
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
    assert events(recorder, "sync.reverify") == []
    updates = events(recorder, "apply.update")
    assert [e["req"] for e in updates] == list(range(1, 13))
    assert {e["args"]["changed"] for e in updates} == {0}


@pytest.mark.parametrize("window", [4, 8])
def test_blocks_above_a_change_are_reverified_and_counted(recorder, window):
    chain = PlacedChain(15, {5: STAKE, 12: MEMBERSHIP})
    reactor, error = sync(chain, window)
    assert error is None and reactor.state.last_block_height == N_BLOCKS
    batched, again, used, lost = counts(recorder)
    assert batched + again == N_BLOCKS
    assert used == batched * N_VALS and lost == again * N_VALS
    # blocks 1..5 are judged under the genesis set, which signed them;
    # the first window above the change was collected before it applied
    assert batched >= 5 and again >= window - 1
    heights = [e["req"] for e in events(recorder, "sync.reverify")]
    assert len(heights) == again and heights == sorted(set(heights))
    assert min(heights) == 6
    # every re-verify is one synchronous verify_commit
    assert len(events(recorder, "commit.collect")) == again
    changed = {e["req"]: e["args"]["changed"]
               for e in events(recorder, "apply.update")}
    assert changed == {h: {5: 1, 12: 2}.get(h, 0)
                       for h in range(1, N_BLOCKS + 1)}


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
