"""Compact consensus gossip (ISSUE 18): salted short ids, strike
backoff, knob off-hatch + legacy-peer byte parity, the mempool
tx-by-hash index, aggregated vote gossip, reconstruction fallback
(hostile fetch peers, timeouts), and compact/legacy mixed-net interop.
"""

import hashlib
import threading
import time

import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.consensus import compact
from tendermint_tpu.consensus.reactor import (
    DATA_CHANNEL,
    VOTE_CHANNEL,
    ConsensusReactor,
    PeerRoundState,
)
from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey, \
    encoding
from tendermint_tpu.types.block import BlockID, PartSetHeader
from tendermint_tpu.types.vote import Vote, VoteType

from tests.test_consensus_reactor import (
    make_validator_node,
    shutdown,
    wait_height,
)


@pytest.fixture(autouse=True)
def _fresh_knobs(monkeypatch):
    """Every test starts from the catalog defaults (auto = on) with no
    env overrides leaking in from the host."""
    monkeypatch.delenv("TM_TPU_COMPACT", raising=False)
    monkeypatch.delenv("TM_TPU_VOTE_AGG", raising=False)
    compact.configure()
    yield
    compact.configure()


@pytest.fixture
def metrics():
    was = telemetry.enabled()
    telemetry.configure(enabled=True)
    yield telemetry.REGISTRY
    # the flag it found, not off: telemetry is process-wide, and xdist
    # runs other files' tests in this worker afterwards
    telemetry.configure(enabled=was)


def _gen(n, chain_id):
    keys = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(n)]
    gen = GenesisDoc(chain_id=chain_id, genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519, 10)
                                 for k in keys])
    return keys, gen


class CapturePeer:
    """Test double recording every send; optionally claims compact
    capabilities (a real peer's caps come from NodeInfo.other)."""

    def __init__(self, pid="capture-peer", caps=()):
        self.id = pid
        self.running = True
        self.sent = []           # (channel, decoded obj)

        class _Info:
            other = list(caps)
        self.node_info = _Info()

    def set(self, k, v):
        pass

    def send(self, ch, raw):
        self.sent.append((ch, encoding.cloads(raw)))
        return True

    def try_send_obj(self, ch, obj):
        self.sent.append((ch, obj))
        return True

    def of_type(self, t):
        return [m for _, m in self.sent if m.get("type") == t]


# ------------------------------------------------------------ short ids

def test_short_ids_deterministic_and_salted():
    sig = b"\x07" * 64
    salt = compact.proposal_salt(sig)
    assert len(salt) == 8
    assert salt == compact.proposal_salt(sig)
    assert salt != compact.proposal_salt(b"\x08" * 64)
    txs = [b"tx-a", b"tx-b", b"tx-a"]
    ids = compact.short_ids_for(salt, txs)
    assert ids[0] == ids[2] != ids[1]
    assert all(len(i) == compact.SHORT_ID_LEN for i in ids)
    # receivers match against FULL stored hashes, never tx bodies
    assert ids[0] == compact.short_id(salt,
                                      hashlib.sha256(b"tx-a").digest())
    # a different proposal's salt permutes every id
    assert compact.short_ids_for(b"\x00" * 8, txs) != ids


# -------------------------------------------------------------- strikes

def test_strike_ledger_exponential_backoff_and_forget():
    led = compact.StrikeLedger(base_s=1.0, cap_s=8.0)
    assert not led.in_backoff("p", 0.0)
    led.strike("p", 0.0, "timeout")          # 1s
    assert led.in_backoff("p", 0.5) and not led.in_backoff("p", 1.5)
    led.strike("p", 10.0, "timeout")         # 2s
    led.strike("p", 20.0, "timeout")         # 4s
    assert led.in_backoff("p", 23.9) and not led.in_backoff("p", 24.1)
    led.strike("p", 30.0, "nack")            # 8s (cap)
    led.strike("p", 40.0, "nack")            # still 8s, capped
    assert led.in_backoff("p", 47.9) and not led.in_backoff("p", 48.1)
    assert not led.in_backoff("q", 0.0)      # per-peer
    led.forget("p")
    assert not led.in_backoff("p", 41.0)


# ---------------------------------------------------------------- knobs

def test_knob_resolution_env_beats_config(monkeypatch):
    assert compact.compact_on() and compact.voteagg_on()   # auto = on
    compact.configure(compact_mode="off", voteagg_mode="off")
    assert not compact.compact_on() and not compact.voteagg_on()
    assert compact.wire_capabilities() == []
    monkeypatch.setenv("TM_TPU_COMPACT", "on")             # env > config
    assert compact.compact_on() and not compact.voteagg_on()
    assert compact.wire_capabilities() == [compact.CAP_COMPACT]
    monkeypatch.setenv("TM_TPU_COMPACT", "off")
    compact.configure()
    assert not compact.compact_on() and compact.voteagg_on()
    assert compact.wire_capabilities() == [compact.CAP_VOTEAGG]


def test_handshake_bytes_identical_with_knobs_off(monkeypatch):
    """Both knobs off: NodeInfo carries NO capability strings — the
    handshake wire bytes are byte-for-byte the legacy shape."""
    from tendermint_tpu.p2p.node_info import NodeInfo
    monkeypatch.setenv("TM_TPU_COMPACT", "off")
    monkeypatch.setenv("TM_TPU_VOTE_AGG", "off")
    pk = PrivKey.generate(b"\x31" * 32).pubkey.ed25519
    legacy = NodeInfo(pubkey=pk, moniker="m", network="n")
    ours = NodeInfo(pubkey=pk, moniker="m", network="n",
                    other=compact.wire_capabilities())
    assert encoding.cdumps(ours.to_obj()) == \
        encoding.cdumps(legacy.to_obj())


def test_reactor_snapshots_knobs_at_construction(monkeypatch):
    keys, gen = _gen(1, "knob-snap")
    monkeypatch.setenv("TM_TPU_COMPACT", "off")
    monkeypatch.setenv("TM_TPU_VOTE_AGG", "off")
    r = ConsensusReactor(make_validator_node(gen, keys[0]))
    assert not r._compact and not r._voteagg
    monkeypatch.setenv("TM_TPU_COMPACT", "auto")
    monkeypatch.setenv("TM_TPU_VOTE_AGG", "auto")
    r2 = ConsensusReactor(make_validator_node(gen, keys[0]))
    assert r2._compact and r2._voteagg
    assert compact.peer_capabilities(
        CapturePeer(caps=[compact.CAP_COMPACT])) == (True, False)
    assert compact.peer_capabilities(object()) == (False, False)


# -------------------------------------------------- mempool hash index

def test_mempool_get_by_hash_lifecycle():
    from tests.test_mempool import make_mempool
    mp, _ = make_mempool()
    txs = [b"idx-tx-%d" % i for i in range(4)]
    for tx in txs:
        mp.check_tx(tx)
    hashes = [hashlib.sha256(tx).digest() for tx in txs]
    for h, tx in zip(hashes, txs):
        assert mp.get_by_hash(h) == tx
    assert set(mp.pending_hashes()) == set(hashes)
    assert mp.get_by_hash(b"\x00" * 32) is None
    # commit two: their index entries drop, the rest survive recheck
    mp.update(1, txs[:2])
    assert mp.get_by_hash(hashes[0]) is None
    assert mp.get_by_hash(hashes[1]) is None
    assert mp.get_by_hash(hashes[2]) == txs[2]
    assert set(mp.pending_hashes()) == set(hashes[2:])
    mp.flush()
    assert mp.pending_hashes() == []


def test_mempool_batch_check_indexes_too():
    from tests.test_mempool import make_mempool
    mp, _ = make_mempool()
    txs = [b"batch-%d" % i for i in range(8)]
    mp.check_tx_batch(txs)
    for tx in txs:
        assert mp.get_by_hash(hashlib.sha256(tx).digest()) == tx


# ------------------------------------------------- vote agg: state side

def _signed_prevotes(keys, gen, cs, round_=0):
    """One nil prevote per validator except cs's own (index 0)."""
    nil = BlockID(b"", PartSetHeader(0, b""))
    votes = []
    for i, k in enumerate(keys):
        if i == 0:
            continue
        v = Vote(validator_address=k.pubkey.address, validator_index=i,
                 height=cs.rs.height, round=round_,
                 type=VoteType.PREVOTE, block_id=nil,
                 timestamp_ns=1000 + i)
        v.signature = k.sign(v.sign_bytes(gen.chain_id))
        votes.append(v)
    return votes


def test_vote_agg_input_applies_whole_batch():
    """A vote_agg submit applies every vote through the bulk VoteSet
    path — same end state as n scalar vote submits."""
    keys, gen = _gen(4, "agg-state")
    cs = make_validator_node(gen, keys[0])
    votes = _signed_prevotes(keys, gen, cs)
    cs.submit({"type": "vote_agg",
               "votes": [v.to_obj() for v in votes]}, "peer-x")
    prevotes = cs.rs.votes.prevotes(0)
    got = {v.validator_index for v in prevotes.votes if v is not None}
    assert {1, 2, 3} <= got
    # duplicates re-delivered in an aggregate are silently absorbed
    cs.submit({"type": "vote_agg",
               "votes": [v.to_obj() for v in votes]}, "peer-y")
    assert {v.validator_index
            for v in cs.rs.votes.prevotes(0).votes
            if v is not None} == got


def test_height_vote_set_bulk_matches_scalar():
    keys, gen = _gen(4, "agg-hvs")
    cs = make_validator_node(gen, keys[0])
    votes = _signed_prevotes(keys, gen, cs)
    results, errors = cs.rs.votes.add_votes(
        0, VoteType.PREVOTE, votes, "peer-z")
    assert results == [True] * 3 and errors == []
    # a second pass is all duplicates: no error, nothing added
    results2, errors2 = cs.rs.votes.add_votes(
        0, VoteType.PREVOTE, votes, "peer-z")
    assert results2 == [False] * 3 and errors2 == []


# ---------------------------------------------- vote agg: gossip bytes

def _reactor_with_votes(chain_id):
    keys, gen = _gen(4, chain_id)
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    votes = _signed_prevotes(keys, gen, cs)
    for v in votes:
        cs.rs.votes.add_vote(v)
    return reactor, cs, votes


def test_legacy_peer_receives_byte_identical_single_votes():
    """Toward a peer that did NOT advertise voteagg/1 the vote pass
    emits exactly the legacy single-vote message — byte-for-byte."""
    reactor, cs, votes = _reactor_with_votes("agg-legacy")
    peer = CapturePeer()                      # no capabilities
    ps = PeerRoundState()
    ps.apply_new_round_step({"height": cs.rs.height, "round": 0,
                             "step": 4})
    reactor.peer_states[peer.id] = ps
    assert reactor._gossip_votes_pass(peer, ps, {"idle": 0})
    ch, msg = peer.sent[0]
    assert ch == VOTE_CHANNEL
    by_index = {v.validator_index: v for v in votes}
    expect = {"type": "vote",
              "vote": by_index[msg["vote"]["validator_index"]].to_obj()}
    assert encoding.cdumps(msg) == encoding.cdumps(expect)


def _register(reactor, peer):
    """Manual peer registration (add_peer would spawn real gossip
    threads against the test double and race the manual passes)."""
    ps = PeerRoundState()
    ps.caps = compact.peer_capabilities(peer)
    reactor.peer_states[peer.id] = ps
    return ps


def test_capable_peer_receives_vote_aggregate():
    reactor, cs, votes = _reactor_with_votes("agg-wire")
    peer = CapturePeer(caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    ps = _register(reactor, peer)
    assert ps.caps == (True, True)
    ps.apply_new_round_step({"height": cs.rs.height, "round": 0,
                             "step": 4})
    assert reactor._gossip_votes_pass(peer, ps, {"idle": 0})
    aggs = peer.of_type("vote_agg")
    assert len(aggs) == 1 and len(aggs[0]["votes"]) == 3
    # every aggregated vote is marked known: the next pass goes idle
    assert not reactor._gossip_votes_pass(peer, ps, {"idle": 0})


def test_voteagg_off_never_aggregates_even_to_capable_peer(monkeypatch):
    monkeypatch.setenv("TM_TPU_VOTE_AGG", "off")
    reactor, cs, votes = _reactor_with_votes("agg-off")
    peer = CapturePeer(caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    ps = _register(reactor, peer)
    ps.apply_new_round_step({"height": cs.rs.height, "round": 0,
                             "step": 4})
    assert reactor._gossip_votes_pass(peer, ps, {"idle": 0})
    assert not peer.of_type("vote_agg")
    assert peer.of_type("vote")


def test_oversized_vote_aggregate_dropped_on_receive():
    keys, gen = _gen(4, "agg-bound")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    peer = CapturePeer()
    reactor.peer_states[peer.id] = PeerRoundState()
    fake = {"height": 1, "round": 0, "type": 1, "validator_index": 1}
    too_many = [dict(fake) for _ in range(compact.MAX_AGG_VOTES + 1)]
    reactor.receive(VOTE_CHANNEL, peer, encoding.cdumps(
        {"type": "vote_agg", "votes": too_many}))
    reactor.receive(VOTE_CHANNEL, peer, encoding.cdumps(
        {"type": "vote_agg", "votes": []}))
    reactor.receive(VOTE_CHANNEL, peer, encoding.cdumps(
        {"type": "vote_agg", "votes": "bogus"}))
    assert cs.rs.votes.prevotes(0).power == 0


# ------------------------------------- compact relay: fallback + hostility

def _compact_msg_for(cs, short_ids, salt=b"\x05" * 8):
    """A plausible compact offer for cs's CURRENT (height, round) with
    attacker-chosen short ids (header content is irrelevant to the
    resolve/fetch phases under test)."""
    return {"type": "compact_block", "height": cs.rs.height,
            "round": cs.rs.round, "salt": salt.hex(),
            "short_ids": [s.hex() for s in short_ids],
            "header": {}, "evidence": [], "last_commit": None}


def test_hostile_peer_never_serves_fetch_falls_back(metrics):
    """A peer advertising txs it never serves: the fetch deadline
    expires, every offerer is nacked (their parts flow), the liar is
    struck, and its NEXT offer is refused while in backoff."""
    keys, gen = _gen(4, "hostile")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    peer = CapturePeer(pid="liar",
                       caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    _register(reactor, peer)
    salt = b"\x05" * 8
    ghost = compact.short_id(salt, hashlib.sha256(b"ghost-tx").digest())
    reactor.receive(DATA_CHANNEL, peer, encoding.cdumps(
        _compact_msg_for(cs, [ghost], salt)))
    # nothing in the mempool matches -> one bounded fetch to the liar
    fetches = peer.of_type("tx_fetch")
    assert len(fetches) == 1 and fetches[0]["indices"] == [0]
    assert reactor._compact_rx is not None
    # ...which is never answered: the deadline nacks and strikes
    reactor._compact_rx["deadline"] = time.monotonic() - 1.0
    reactor._compact_rx_tick(time.monotonic())
    assert reactor._compact_rx is None
    nacks = [m for m in peer.of_type("compact_ack") if not m["ok"]]
    assert len(nacks) == 1
    assert reactor._strikes.in_backoff("liar", time.monotonic())
    # while in backoff, further offers are refused outright
    reactor.receive(DATA_CHANNEL, peer, encoding.cdumps(
        _compact_msg_for(cs, [ghost], salt)))
    assert reactor._compact_rx is None
    assert len([m for m in peer.of_type("compact_ack")
                if not m["ok"]]) == 2
    assert metrics.value("compact_reconstruct_total",
                         {"outcome": "fallback"}) >= 1


def test_bogus_fetch_reply_strikes_and_falls_back():
    """A fetch reply whose tx does not hash to the advertised short id
    is a lying sender: strike + immediate fallback, never a rebuilt
    block from unverified bytes."""
    keys, gen = _gen(4, "bogus")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    peer = CapturePeer(pid="forger",
                       caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    _register(reactor, peer)
    salt = b"\x06" * 8
    ghost = compact.short_id(salt, hashlib.sha256(b"real-tx").digest())
    reactor.receive(DATA_CHANNEL, peer, encoding.cdumps(
        _compact_msg_for(cs, [ghost], salt)))
    assert peer.of_type("tx_fetch")
    reactor.receive(DATA_CHANNEL, peer, encoding.cdumps(
        {"type": "tx_fetch_reply", "height": cs.rs.height,
         "round": cs.rs.round, "txs": [[0, b"WRONG-tx".hex()]]}))
    assert reactor._compact_rx is None
    assert reactor._strikes.in_backoff("forger", time.monotonic())
    assert [m for m in peer.of_type("compact_ack") if not m["ok"]]


def test_stale_compact_offer_nacked():
    keys, gen = _gen(4, "stale")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    peer = CapturePeer(pid="slow",
                       caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    _register(reactor, peer)
    msg = _compact_msg_for(cs, [])
    msg["height"] = cs.rs.height + 7
    reactor.receive(DATA_CHANNEL, peer, encoding.cdumps(msg))
    assert reactor._compact_rx is None
    assert [m for m in peer.of_type("compact_ack") if not m["ok"]]
    # a stale offer is not the peer's fault: no strike
    assert not reactor._strikes.in_backoff("slow", time.monotonic())


def test_benign_nack_never_strikes_fault_nack_does():
    """Sender side: a stale/backoff nack is routine at round edges and
    must not open a backoff window (one stale offer would otherwise
    cascade into mutual backoff); a fault nack (reconstruction failed)
    still strikes."""
    keys, gen = _gen(4, "nack-kind")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    peer = CapturePeer(pid="edge",
                       caps=[compact.CAP_COMPACT, compact.CAP_VOTEAGG])
    ps = _register(reactor, peer)
    key = (cs.rs.height, cs.rs.round)
    now = time.monotonic()
    for reason in ("stale", "backoff", "busy"):
        with reactor._compact_lock:
            reactor._compact_sent["edge"] = {
                "key": key, "deadline": now + 10.0}
        reactor._on_compact_ack(peer, ps, {
            "height": key[0], "round": key[1], "ok": False,
            "reason": reason})
        assert not reactor._strikes.in_backoff("edge", now), reason
        # the entry is written off either way: parts flow, no re-offer
        assert reactor._compact_sent["edge"]["done"]
    with reactor._compact_lock:
        reactor._compact_sent["edge"] = {
            "key": key, "deadline": now + 10.0}
    reactor._on_compact_ack(peer, ps, {
        "height": key[0], "round": key[1], "ok": False,
        "reason": "failed"})
    assert reactor._strikes.in_backoff("edge", now)


def test_compact_sender_timeout_strikes_and_ships_parts():
    """Sender side: an unacked offer past its deadline flips that peer
    to the parts path (and a strike suppresses re-offering)."""
    keys, gen = _gen(4, "sender-to")
    cs = make_validator_node(gen, keys[0])
    reactor = ConsensusReactor(cs)
    ps = PeerRoundState()
    peer = CapturePeer(pid="quiet")
    now = time.monotonic()
    with reactor._compact_lock:
        reactor._compact_sent["quiet"] = {
            "key": (cs.rs.height, cs.rs.round), "deadline": now - 1.0}
    with cs._lock:
        mode, msg = reactor._compact_tx_phase(peer, ps, cs.rs, now)
    assert (mode, msg) == ("parts", None)
    assert reactor._strikes.in_backoff("quiet", now)
    with cs._lock:   # struck: no fresh offer either
        mode, _ = reactor._compact_tx_phase(peer, ps, cs.rs, now)
    assert mode == "parts"


# ------------------------------------------------------ net integration

def _make_capable_net(n, chain_id, caps_for):
    """make_connected_switches, but node i's NodeInfo advertises
    caps_for(i) — the real handshake negotiates the compact plane."""
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.p2p.node_info import NodeInfo
    from tendermint_tpu.p2p.switch import Switch
    from tendermint_tpu.p2p.test_util import connect_switches

    keys, gen = _gen(n, chain_id)
    css = [make_validator_node(gen, k, with_mempool=True) for k in keys]
    reactors = [ConsensusReactor(cs, gossip_sleep_s=0.005) for cs in css]
    switches = []
    for i in range(n):
        nk = NodeKey(PrivKey.generate(bytes([0x40 + i]) * 32))
        info = NodeInfo(pubkey=nk.pubkey, moniker=f"node{i}",
                        network=chain_id, other=list(caps_for(i)))
        sw = Switch(P2PConfig(), nk, info)
        sw.add_reactor("consensus", reactors[i])
        sw.start()
        switches.append(sw)
    for i in range(n):
        for j in range(i + 1, n):
            connect_switches(switches[i], switches[j])
    return css, reactors, switches


def _warm_mempools(css, txs):
    for cs in css:
        for tx in txs:
            try:
                cs.mempool.check_tx(tx)
            except Exception:
                pass


def test_compact_net_converges_with_reconstruction(metrics):
    """All-capable 4-node net with warm mempools: blocks flow through
    the compact plane (reconstructions recorded), votes aggregate, the
    chain converges on one tip, and app state matches everywhere."""
    all_caps = [compact.CAP_COMPACT, compact.CAP_VOTEAGG]
    css, reactors, switches = _make_capable_net(
        4, "compact-net", lambda i: all_caps)
    try:
        for r in reactors:
            for ps in r.peer_states.values():
                assert ps.caps == (True, True)
        assert wait_height(css, 1)
        _warm_mempools(css, [b"compact=yes", b"agg=yes"])
        base = max(cs.state.last_block_height for cs in css)
        assert wait_height(css, base + 3), (
            f"heights: {[cs.state.last_block_height for cs in css]}")
        tips = {cs.state.last_block_id.key() for cs in css
                if cs.state.last_block_height ==
                css[0].state.last_block_height}
        assert len(tips) == 1
        assert all(cs.app.store.get(b"compact") == b"yes" for cs in css)
        recon = sum(
            metrics.value("compact_reconstruct_total", {"outcome": o})
            or 0 for o in ("hit", "fetched"))
        assert recon > 0, "no block ever travelled compact"
        assert (metrics.value("voteagg_msgs_sent_total") or 0) > 0
        agg = metrics.value("voteagg_batch_votes")
        assert agg and agg["count"] > 0 and \
            agg["sum"] / agg["count"] > 1.0
    finally:
        shutdown(reactors, switches)


def test_mixed_compact_legacy_net_converges():
    """Interop both directions: two capable + two legacy nodes commit
    together; capable->legacy traffic stays legacy-shaped, and txs
    still reach every app."""
    all_caps = [compact.CAP_COMPACT, compact.CAP_VOTEAGG]
    css, reactors, switches = _make_capable_net(
        4, "mixed-net", lambda i: all_caps if i < 2 else [])
    try:
        # capable nodes see the legacy half as (False, False)
        for i in (0, 1):
            caps_seen = sorted(ps.caps
                               for ps in reactors[i].peer_states.values())
            assert caps_seen == [(False, False), (False, False),
                                 (True, True)]
        assert wait_height(css, 1)
        _warm_mempools(css, [b"mixed=net"])
        base = max(cs.state.last_block_height for cs in css)
        assert wait_height(css, base + 3), (
            f"heights: {[cs.state.last_block_height for cs in css]}")
        tips = {cs.state.last_block_id.key() for cs in css
                if cs.state.last_block_height ==
                css[0].state.last_block_height}
        assert len(tips) == 1
        assert all(cs.app.store.get(b"mixed") == b"net" for cs in css)
    finally:
        shutdown(reactors, switches)


def test_knobs_off_net_sends_zero_compact_messages(monkeypatch):
    """Both knobs off: even a fully capable-peer net never puts a new
    message type on the wire — the traffic is the legacy shape."""
    monkeypatch.setenv("TM_TPU_COMPACT", "off")
    monkeypatch.setenv("TM_TPU_VOTE_AGG", "off")
    seen = []
    orig = ConsensusReactor.receive

    def spying_receive(self, ch_id, peer, msg_bytes):
        seen.append(encoding.cloads(msg_bytes).get("type"))
        return orig(self, ch_id, peer, msg_bytes)

    monkeypatch.setattr(ConsensusReactor, "receive", spying_receive)
    all_caps = [compact.CAP_COMPACT, compact.CAP_VOTEAGG]
    css, reactors, switches = _make_capable_net(
        3, "off-net", lambda i: all_caps)
    try:
        assert all(not r._compact and not r._voteagg for r in reactors)
        assert wait_height(css, 2)
        legacy = {"proposal", "block_part", "vote", "new_round_step",
                  "has_vote", "commit_step", "heartbeat",
                  "vote_set_maj23", "vote_set_bits"}
        assert set(seen) <= legacy, sorted(set(seen) - legacy)
    finally:
        shutdown(reactors, switches)


# ------------------------------------------------------------ wire chaos

@pytest.mark.slow
def test_compact_plane_survives_wire_faults():
    """The compact plane under the PR 13 TCP fault proxy (drop + delay
    + corruption on every link): the net keeps committing, converges
    on one tip, and any reconstruction that the faults break falls
    back without wedging a peer (no stall = heights advance within the
    budget)."""
    from tendermint_tpu.chaos.wire import WireProxy, WireSchedule
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.p2p import NetAddress
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.p2p.node_info import NodeInfo
    from tendermint_tpu.p2p.switch import Switch

    n = 4
    caps = [compact.CAP_COMPACT, compact.CAP_VOTEAGG]
    keys, gen = _gen(n, "wire-compact")
    css = [make_validator_node(gen, k, with_mempool=True) for k in keys]
    reactors = [ConsensusReactor(cs, gossip_sleep_s=0.005) for cs in css]
    switches = []
    for i in range(n):
        nk = NodeKey(PrivKey.generate(bytes([0x60 + i]) * 32))
        info = NodeInfo(pubkey=nk.pubkey, moniker=f"node{i}",
                        network="wire-compact", other=list(caps))
        sw = Switch(P2PConfig(), nk, info, encrypt=True)
        sw._ban_score = 0          # corrupt frames must not ban peers
        sw.add_reactor("consensus", reactors[i])
        switches.append(sw)
    addrs = [sw.listen("127.0.0.1", 0) for sw in switches]
    spec = {"drop": 0.01, "delay": 0.05, "delay_steps": [1, 2],
            "corrupt": 0.001, "step_ms": 20}
    sched = WireSchedule(spec, seed=18, n_nodes=n)
    mapping = {(i, j): ("127.0.0.1", addrs[j].port)
               for i in range(n) for j in range(n) if i < j}
    proxy = WireProxy(sched, mapping)
    ports = proxy.listen()
    proxy.start()
    try:
        for sw in switches:
            sw.start()
        for (i, j), port in ports.items():
            switches[i].dial_peer(
                NetAddress("127.0.0.1", port, switches[j].node_info.id),
                persistent=True)
        proxy.arm()
        _warm_mempools(css, [b"wire=chaos"])
        assert wait_height(css, 3, timeout=120.0), (
            f"stalled under wire faults: "
            f"{[cs.state.last_block_height for cs in css]}")
        top = min(cs.state.last_block_height for cs in css)
        ids = {cs.block_store.load_block_meta(top).block_id.key()
               for cs in css}
        assert len(ids) == 1, "chain divergence under wire faults"
    finally:
        for sw in switches:
            sw.stop()
        proxy.stop()
