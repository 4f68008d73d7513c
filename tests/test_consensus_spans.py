"""A height on the program's own recorder (telemetry/trace.py): what
PROPOSE and COMMIT are made of (`cs:propose.*`, `cs:commit.*`), why a
round is lost (`cs:nil_vote`, `queue.saturated`, `gc.collect`) and the
front door's two waits (`tm_rpc_queue_seconds`, `tm_rpc_reply_seconds`).
The nets are tests/test_consensus.py's: N state machines wired through
their broadcast hooks, timeouts fired by hand."""

import gc
import threading
import time

import pytest

import tests.test_consensus as tc
from tendermint_tpu import telemetry
from tendermint_tpu.consensus import Step
from tendermint_tpu.consensus import state as cstate
from tendermint_tpu.telemetry import causal, trace

G = "gossip and consensus rounds"
NEW_SPANS = {
    "cs:propose.build": G, "cs:propose.send": G,
    "cs:propose.await_proposal": G, "cs:propose.await_block": G,
    "cs:commit.validate": G, "cs:commit.persist": G,
    "cs:nil_vote": G, "queue.saturated": G, "gc.collect": "host runtime",
}
HEIGHTS = (1, 2, 3)


@pytest.fixture
def ring(monkeypatch):
    """A tracer of this test's own in the program's place, telemetry
    on; afterwards as found."""
    t = trace.Tracer(capacity=1 << 14)
    monkeypatch.setattr(trace, "TRACER", t)
    monkeypatch.setattr(telemetry, "TRACER", t)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    yield t
    telemetry.set_enabled(was)


def by_name(t, name, **args):
    return [e for e in t.events() if e["name"] == name and
            all(e.get("args", {}).get(k) == v for k, v in args.items())]


def end(ev):
    return ev["ts"] + ev.get("dur", 0.0)


@pytest.fixture(params=["pipelined", "serial"])
def run3(request, ring, monkeypatch):
    """Four validators through three heights, on the pipelined commit
    path and on the serial one; (tracer, nodes, {height: proposer's
    node id})."""
    monkeypatch.setenv("TM_TPU_PIPELINE",
                       "on" if request.param == "pipelined" else "off")
    nodes, _ = tc.make_net(4)
    assert all(n._pipeline == (request.param == "pipelined") for n in nodes)
    of = {n.priv_validator.address: n._trace_node for n in nodes}
    proposers = {}
    for n in nodes:
        n.start()
    for h in HEIGHTS:
        tc.run_until_height(nodes, h - 1)
        while any(n.rs.height < h for n in nodes):
            tc.fire_all(nodes)
        proposers[h] = of[nodes[0].rs.validators.proposer().address]
        tc.run_until_height(nodes, h)
    return ring, nodes, proposers


# ------------------------------------------------------- the catalogue

@pytest.mark.parametrize("name", sorted(NEW_SPANS))
def test_the_catalogue_names_each_new_span_with_its_layer(name):
    assert trace.SPANS[name] == NEW_SPANS[name]


def test_the_table_of_marks_holds_to_both_catalogues():
    assert set(cstate._RECORDER_NAME) <= causal.SPAN_CATALOG
    named = {v for v in cstate._RECORDER_NAME.values() if v is not None}
    assert named == {"cs:propose.await_proposal", "cs:propose.await_block",
                     "cs:finalize_commit"}
    assert named <= set(trace.SPANS)


def test_the_lint_holds_the_helpers_names_to_the_catalogues(tmp_path):
    from tendermint_tpu.analysis.checkers import metrics as mcheck
    (tmp_path / "bad.py").write_text(
        'self._cspan("cs:propose.build", 1)\n'
        'self._cspan("cs:propose.bogus", 1)\n'
        'self._cwait("block.full", 1, 0, since=None)\n'
        'self._cwait("block.fuller", 1, 0, since=None)\n'
        'self._cpoint("cs:nil_vote", 1, 0, why="x")\n'
        'self._cpoint("cs:nil_votes", 1, 0)\n')
    got = [(f.line, f.message.split("'")[1], f.message.rsplit(" ", 1)[1])
           for f in mcheck.span_findings(str(tmp_path))]
    assert got == [(2, "cs:propose.bogus", "telemetry.trace.SPANS"),
                   (4, "block.fuller", "telemetry.causal.SPAN_CATALOG"),
                   (6, "cs:nil_votes", "telemetry.trace.SPANS")]
    assert mcheck.span_findings() == []


# ------------------------------------------- what a height is made of

def test_the_proposer_alone_builds_and_sends_once_a_height(run3):
    t, nodes, proposers = run3
    for h in HEIGHTS:
        for name in ("cs:propose.build", "cs:propose.send"):
            evs = [e for e in by_name(t, name) if e["req"] == h]
            assert [e["args"]["node"] for e in evs] == [proposers[h]], \
                (name, h)
            assert evs[0]["args"]["round"] == 0
        (build,) = [e for e in by_name(t, "cs:propose.build")
                    if e["req"] == h]
        (send,) = [e for e in by_name(t, "cs:propose.send") if e["req"] == h]
        assert build["args"]["txs"] == 0 and send["args"]["parts"] == 1
        # _enter_propose changes the step last: the proposer's two lie
        # before its PROPOSE step opens, build before send
        (step,) = [e for e in by_name(t, "cs:PROPOSE", node=proposers[h])
                   if e["req"] == h]
        assert end(build) <= send["ts"] and end(send) <= step["ts"]
        assert send["parent"] == 0 and build["id"] != send["id"]


def test_each_other_node_waits_once_for_the_proposal_and_its_block(run3):
    t, nodes, proposers = run3
    ids = {n._trace_node for n in nodes}
    for h in HEIGHTS:
        others = ids - {proposers[h]}
        for name in ("cs:propose.await_proposal", "cs:propose.await_block"):
            evs = [e for e in by_name(t, name) if e["req"] == h]
            assert sorted(e["args"]["node"] for e in evs) == sorted(others)
            for e in evs:
                assert e["ph"] == "X" and e["args"]["round"] == 0
                # inside the node's PROPOSE step, or a wait of 0 s
                # where what it waited for came before the step began
                (step,) = [s for s in by_name(t, "cs:PROPOSE",
                                              node=e["args"]["node"])
                           if s["req"] == h]
                assert e["dur"] == 0.0 or (
                    step["ts"] - 1.0 <= e["ts"] and
                    end(e) <= end(step) + 1.0), (name, e, step)
        for node in others:
            (got,) = by_name(t, "cs:propose.await_block", node=node,
                             parts=1)[h - 1:h]
            (first,) = [e for e in by_name(t, "cs:propose.await_proposal",
                                           node=node) if e["req"] == h]
            assert end(first) <= got["ts"] + 1.0


def test_every_node_validates_and_persists_once_inside_its_commit(run3):
    t, nodes, _ = run3
    for h in HEIGHTS:
        for n in nodes:
            (step,) = [s for s in by_name(t, "cs:COMMIT",
                                          node=n._trace_node)
                       if s["req"] == h]
            marks = []
            for name in ("cs:commit.validate", "cs:commit.persist"):
                (ev,) = [e for e in by_name(t, name, node=n._trace_node)
                         if e["req"] == h]
                assert step["ts"] <= ev["ts"] and end(ev) <= end(step) + 1.0
                marks.append(ev)
            assert end(marks[0]) <= marks[1]["ts"]
            # the commit point is the same call's: one instant a height
            (done,) = [e for e in by_name(t, "cs:finalize_commit",
                                          node=n._trace_node)
                       if e["req"] == h]
            assert done["ph"] == "i" and done["args"]["txs"] == 0


def test_a_healthy_run_signs_no_nil_vote(run3):
    t, _nodes, _ = run3
    assert by_name(t, "cs:nil_vote") == []
    assert by_name(t, "cs:timeout") == []


def test_one_call_writes_both_timelines(ring, monkeypatch):
    """TM_TPU_TRACE on beside telemetry: causal's ring gets its points
    by its names, the recorder its events, from the same calls."""
    monkeypatch.setenv("TM_TPU_TRACE", "on")
    causal.clear()
    try:
        nodes, _ = tc.make_net(4)
        for n in nodes:
            n.start()
        tc.run_until_height(nodes, 1)
        names = [ev["n"] for ev in causal.dump()["spans"] if ev["h"] == 1]
        for name in ("height.begin", "propose", "proposal.recv", "part.first",
                     "block.full", "quorum.prevote", "quorum.precommit",
                     "flush", "wal.fsync", "commit"):
            assert names.count(name) == (1 if name == "propose" else 4), name
        assert not [n for n in names if n.startswith("cs:")]
        assert len(by_name(ring, "cs:propose.await_block")) == 3
        assert len(by_name(ring, "cs:finalize_commit")) == 4
    finally:
        causal.clear()


def test_a_replayed_height_leaves_no_mark(ring):
    nodes, _ = tc.make_net(1)
    cs = nodes[0]
    cs.replay_mode = True
    cs.start()
    tc.run_until_height(nodes, 1)
    # (`cs:vote_ingest` is the vote set's own, PR 31)
    assert {e["name"] for e in ring.events()
            if e["name"].startswith("cs:")} <= {"cs:vote_ingest"}


# ------------------------------------------------ why a round is lost

def alone(nodes, proposer: bool, hears=None):
    """One validator of four that hears nobody, round 0 open: at its
    PROPOSE step, or, the proposer, at PREVOTE with its own block."""
    addr = nodes[0].rs.validators.proposer().address
    cs = next(n for n in nodes
              if (n.priv_validator.address == addr) == proposer)
    cs.broadcast_hooks[:] = [] if hears is None else [hears.append]
    cs.start()
    cs.ticker.fire_next()           # the commit timeout: round 0 opens
    assert cs.rs.step == (Step.PREVOTE if proposer else Step.PROPOSE)
    return cs


def test_a_silent_proposer_costs_a_nil_prevote_that_says_no_proposal(ring):
    nodes, _ = tc.make_net(4)
    cs = alone(nodes, proposer=False)
    cs.ticker.fire_next()           # the propose timeout
    assert cs.rs.step >= Step.PREVOTE
    (fired,) = by_name(ring, "cs:timeout", node=cs._trace_node)
    (nil,) = by_name(ring, "cs:nil_vote", node=cs._trace_node)
    assert nil["ph"] == "i" and nil["req"] == 1
    assert nil["args"] == {"type": "prevote", "round": 0, "why": "no_proposal",
                           "node": cs._trace_node}
    assert fired["ts"] <= nil["ts"]
    # a step that ended in its timeout records neither wait
    assert by_name(ring, "cs:propose.await_proposal") == []
    assert by_name(ring, "cs:propose.await_block") == []


def test_a_proposal_whose_block_never_came_says_no_block(ring):
    nodes, _ = tc.make_net(4)
    cs = alone(nodes, proposer=False)
    sent = []
    alone(nodes, proposer=True, hears=sent)
    proposal = next(m for m in sent if m["type"] == "proposal")
    cs.submit(dict(proposal), peer_id="p")
    assert cs.rs.proposal is not None and cs.rs.proposal_block is None
    (got,) = by_name(ring, "cs:propose.await_proposal", node=cs._trace_node)
    assert got["req"] == 1 and got["dur"] > 0.0
    cs.ticker.fire_next()                   # the propose timeout
    (nil,) = by_name(ring, "cs:nil_vote", node=cs._trace_node)
    assert (nil["args"]["type"], nil["args"]["why"]) == ("prevote", "no_block")
    assert by_name(ring, "cs:propose.await_block", node=cs._trace_node) == []


def nil_vote(key, cs, type_):
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.types.vote import Vote
    i, _ = cs.rs.validators.get_by_address(key.pubkey.address)
    v = Vote(key.pubkey.address, i, cs.rs.height, cs.rs.round, 11 + i, type_,
             BlockID())
    v.signature = key.sign(v.sign_bytes(cs.state.chain_id))
    return {"type": "vote", "vote": v.to_obj()}


@pytest.mark.parametrize("nil_peers, why", [(2, "no_polka"),
                                            (3, "polka_nil")])
def test_a_nil_precommit_says_which_polka_it_lacked(ring, nil_peers, why):
    """The proposer prevotes its own block; two peers' nil prevotes
    make +2/3 of any kind and no polka (the prevote-wait timeout then
    costs a nil precommit), three make a polka for nil."""
    from tendermint_tpu.types.vote import VoteType
    nodes, keys = tc.make_net(4)
    cs = alone(nodes, proposer=True)
    peers = [k for k in keys
             if k.pubkey.address != cs.priv_validator.address]
    for key in peers[:nil_peers]:
        cs.submit(nil_vote(key, cs, VoteType.PREVOTE), "p")
    if cs.rs.step == Step.PREVOTE_WAIT:
        cs.ticker.fire_next()               # the prevote-wait timeout
    assert cs.rs.step >= Step.PRECOMMIT
    (nil,) = by_name(ring, "cs:nil_vote", node=cs._trace_node)
    assert nil["args"] == {"type": "precommit", "round": 0, "why": why,
                           "node": cs._trace_node}


def test_a_block_that_does_not_validate_says_invalid_block(ring,
                                                           monkeypatch):
    from tendermint_tpu.state.validation import BlockValidationError
    nodes, _ = tc.make_net(4)
    addr = nodes[0].rs.validators.proposer().address
    cs = next(n for n in nodes if n.priv_validator.address != addr)

    real, calls = cs.block_exec.validate_block, []

    def refuse_once(state, block, **kw):
        calls.append(block)
        if len(calls) == 1:
            raise BlockValidationError("refused by the test")
        return real(state, block, **kw)
    monkeypatch.setattr(cs.block_exec, "validate_block", refuse_once)
    for n in nodes:
        n.start()
    for n in nodes:
        n.ticker.fire_next()
    (nil,) = by_name(ring, "cs:nil_vote", node=cs._trace_node,
                     type="prevote")
    assert nil["args"]["why"] == "invalid_block"
    # the proposal and its block came in time: both waits are recorded
    assert len(by_name(ring, "cs:propose.await_proposal",
                       node=cs._trace_node)) == 1
    assert len(by_name(ring, "cs:propose.await_block",
                       node=cs._trace_node)) == 1


def test_a_saturated_queue_leaves_one_instant_beside_its_counter(ring):
    from tendermint_tpu.telemetry import queues
    before = telemetry.value("queue_saturation_events_total",
                             {"queue": "mconn.send.0x22"}) or 0.0
    queues._fire("mconn.send.0x22", 0.91, 91)
    (ev,) = by_name(ring, "queue.saturated")
    assert ev["ph"] == "i"
    assert ev["args"] == {"queue": "mconn.send.0x22", "depth": 91}
    assert telemetry.value("queue_saturation_events_total",
                           {"queue": "mconn.send.0x22"}) == before + 1


# --------------------------------------------------------- the collector

def gc_paused():
    return {gen: telemetry.value("gc_pause_seconds_total",
                                 {"gen": gen}) or 0.0 for gen in "012"}


@pytest.fixture
def tracked():
    """A few hundred thousand objects for the collector to walk, made
    with the automatic collections off: every collection the test sees
    is one it asked for."""
    gc.collect()
    gc.disable()
    try:
        yield [[i] for i in range(400_000)]
    finally:
        gc.enable()


def test_a_long_collection_leaves_an_event_and_a_short_one_only_counts(
        ring, tracked):
    assert trace._on_gc in gc.callbacks
    gc.collect(0)                   # the young ones move on
    ring.clear()
    c0 = gc_paused()
    gc.collect(0)                   # nothing to walk: microseconds
    c1 = gc_paused()
    assert by_name(ring, "gc.collect") == []
    assert c1["0"] > c0["0"] and (c1["1"], c1["2"]) == (c0["1"], c0["2"])
    with telemetry.span("lite.votes", req=7) as outer:
        t0 = time.perf_counter()
        gc.collect()
        took = time.perf_counter() - t0
    assert took >= trace.GC_EVENT_MIN_S
    (ev,) = by_name(ring, "gc.collect")
    assert ev["ph"] == "X" and ev["dur"] >= 1e6 * trace.GC_EVENT_MIN_S
    # `collected`: whatever garbage the tests before this one left
    assert ev["args"]["gen"] == 2 and ev["args"]["collected"] >= 0
    # on the thread it stopped, inside the span that was open there
    assert ev["parent"] == outer.id and ev["req"] == 7
    c2 = gc_paused()
    assert c2["2"] - c1["2"] == pytest.approx(ev["dur"] / 1e6, rel=0.05)
    assert (c2["0"], c2["1"]) == (c1["0"], c1["1"])


def test_a_collection_inside_the_ring_s_own_lock_never_waits(ring, tracked):
    """The thread a collection stops may hold the ring's lock: the
    callback then leaves the event for the lock's next holder, so none
    is lost."""
    ring.clear()
    c0 = gc_paused()
    with telemetry.span("sync.apply", req=3) as outer:
        with ring._lock:
            gc.collect()
            assert ring._written == 0 and len(ring._late) == 1
    # the span's own end wrote it, after itself
    assert ring._late == []
    (ev,) = by_name(ring, "gc.collect")
    assert [e["name"] for e in ring.events()] == ["sync.apply", "gc.collect"]
    assert ev["parent"] == outer.id and ev["req"] == 3
    assert ev["tid"] == threading.get_ident()
    assert gc_paused()["2"] - c0["2"] == pytest.approx(ev["dur"] / 1e6,
                                                       rel=0.05)
    # a reader takes what no writer came for
    with ring._lock:
        gc.collect()
    assert len(ring._late) == 1
    assert len(ring.between("gc.collect", 0.0, time.perf_counter())[0]) == 2
    with ring._lock:
        gc.collect()
    ring.clear()
    assert ring._late == [] and ring.events() == []


def test_the_collector_s_callback_takes_no_lock_of_the_family(ring):
    """`_Family.children` allocates under the family's lock, so a
    collection may begin on the thread that holds it: the three
    children are there before the first collection is counted."""
    fam = telemetry.REGISTRY.get("gc_pause_seconds_total")
    assert [labels for labels, _ in sorted(fam.children())] == [
        ("0",), ("1",), ("2",)]
    c0 = gc_paused()
    with fam._lock:
        gc.collect()
    assert gc_paused()["2"] > c0["2"]


# ------------------------------------------------------- telemetry off

def test_switched_off_nothing_is_recorded_called_or_counted(ring):
    found = [cb for cb in gc.callbacks if cb is not trace._on_gc]
    telemetry.set_enabled(False)
    try:
        assert gc.callbacks == found
        next_id = next(trace._ids)
        c0 = gc_paused()
        nodes, _ = tc.make_net(4)
        for n in nodes:
            n.start()
        tc.run_until_height(nodes, 2)
        gc.collect()
        from tendermint_tpu.telemetry import queues
        queues._fire("mconn.send.0x20", 0.9, 9)
        assert ring.events() == []
        assert next(trace._ids) == next_id + 1      # none was taken
        assert gc_paused() == c0
    finally:
        telemetry.set_enabled(True)
    assert gc.callbacks == found + [trace._on_gc]
    telemetry.configure(enabled=False)
    assert trace._on_gc not in gc.callbacks
    telemetry.configure(enabled=True)
    assert gc.callbacks.count(trace._on_gc) == 1


# ------------------------------------------- the front door's two waits

def waits(route):
    return [telemetry.value(name, {"route": route}) or
            {"count": 0, "sum": 0.0}
            for name in ("rpc_queue_seconds", "rpc_reply_seconds")]


@pytest.fixture
def front_door():
    from tendermint_tpu.p2p.conn.loop import ReactorLoop
    from tendermint_tpu.rpc.aserver import AsyncRPCServer
    from tendermint_tpu.rpc.client import JSONRPCClient
    loop = ReactorLoop(name="tm-reactor-loop-test")
    loop.start()
    srv = AsyncRPCServer(loop, workers=1)
    srv.register("nap", lambda ms=0: time.sleep(int(ms) / 1e3) or "up")
    host, port = srv.serve("127.0.0.1", 0)
    yield srv, JSONRPCClient(f"http://{host}:{port}")
    srv.stop()
    loop.stop()


def test_a_call_is_timed_before_and_after_its_handler(front_door):
    _srv, client = front_door
    q0, r0 = waits("nap")
    assert client.call("nap", ms=30) == "up"
    q1, r1 = waits("nap")
    assert (q1["count"], r1["count"]) == (q0["count"] + 1, r0["count"] + 1)
    # neither wait holds the handler's 30 ms
    assert q1["sum"] - q0["sum"] < 0.025 and r1["sum"] - r0["sum"] < 0.025
    call = telemetry.value("rpc_call_seconds", {"route": "nap", "chain": ""})
    assert call["sum"] >= 0.03
    assert 1e-3 in q1["buckets"] and 2.5e-3 in r1["buckets"]


def test_a_busy_pool_shows_in_the_queue_wait_alone(front_door):
    import threading
    _srv, client = front_door
    q0, r0 = waits("nap")
    first = threading.Thread(target=client.call, args=("nap",),
                             kwargs={"ms": 150})
    first.start()
    time.sleep(0.05)                # the one worker is taken
    from tendermint_tpu.rpc.client import JSONRPCClient
    assert JSONRPCClient(client.address).call("nap", ms=0) == "up"
    first.join(timeout=10.0)
    assert not first.is_alive()
    q1, r1 = waits("nap")
    assert q1["count"] == q0["count"] + 2
    assert q1["sum"] - q0["sum"] >= 0.05        # waited for the worker
    assert r1["sum"] - r0["sum"] < 0.05


def test_an_unknown_method_is_one_route_and_off_is_no_sample(front_door):
    _srv, client = front_door
    q0, r0 = waits("unknown")
    with pytest.raises(Exception):
        client.call("no_such_" + str(time.time_ns()))
    q1, r1 = waits("unknown")
    assert (q1["count"], r1["count"]) == (q0["count"] + 1, r0["count"] + 1)
    n0 = [w["count"] for w in waits("nap")]
    telemetry.set_enabled(False)
    try:
        assert client.call("nap", ms=0) == "up"
    finally:
        telemetry.set_enabled(True)
    assert [w["count"] for w in waits("nap")] == n0
