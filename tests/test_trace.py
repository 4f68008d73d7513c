"""Causal tracing plane (telemetry/causal.py + telemetry/merge.py):
wire-format equivalence with TM_TPU_TRACE off, cross-node trace-id
propagation over a real 2-node TCP net, ring cap + drop accounting,
stall-detector flight recorder, clock alignment on synthetic skewed
inputs, attribution table, span-name catalog lint, RPC/debug surface,
and the keepalive RTT sample the merger cross-checks against."""

import json
import socket
import time
import urllib.request

import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.telemetry import causal, merge
from tendermint_tpu.telemetry import trace as ttrace
from tendermint_tpu.types import encoding


@pytest.fixture(autouse=True)
def _trace_reset(monkeypatch):
    """The causal plane is process-global state (ring, node id,
    configure snapshot); every test starts from the off/empty state."""
    monkeypatch.delenv("TM_TPU_TRACE", raising=False)
    causal.configure("off")
    causal.clear()
    causal.set_capacity(None)
    causal.set_node("")
    causal.set_rtt_provider(None)
    yield
    causal.configure("off")
    causal.clear()
    causal.set_capacity(None)
    causal.set_node("")
    causal.set_rtt_provider(None)


# the envelope kinds the reactors stamp (consensus DATA/VOTE/STATE
# channels + mempool tx gossip), in their exact PR 7 wire shapes
_ENVELOPES = [
    {"type": "proposal", "proposal": {"height": 7, "round": 0,
                                      "block_parts_header":
                                          {"total": 3, "hash": "aa"}}},
    {"type": "block_part", "height": 7, "round": 0,
     "part": {"index": 1, "bytes": "00ff", "proof": []}},
    {"type": "vote", "vote": {"height": 7, "round": 0, "type": 1,
                              "validator_index": 2}},
    {"type": "new_round_step", "height": 7, "round": 0, "step": 3,
     "last_commit_round": 0},
    {"type": "has_vote", "height": 7, "round": 0, "vote_type": 1,
     "index": 2},
    {"type": "txs", "txs": ["aabb", "ccdd"]},
]


def test_wire_bytes_identical_when_off():
    """TM_TPU_TRACE off: stamp() must return the envelope object
    UNTOUCHED — encoded wire bytes byte-for-byte the untraced format
    for every stamped message kind."""
    assert not causal.enabled()
    for msg in _ENVELOPES:
        baseline = encoding.cdumps(msg)
        out = causal.stamp(msg, 7, 0)
        assert out is msg, msg["type"]
        assert "tr" not in msg
        assert encoding.cdumps(out) == baseline, msg["type"]
        # receive side: take() on an untraced envelope is a no-op
        before = dict(msg)
        assert causal.take(msg, msg["type"]) is None
        assert msg == before


def test_stamp_take_roundtrip_on():
    causal.configure("on")
    causal.set_node("origin-node")
    msg = dict(_ENVELOPES[2])
    out = causal.stamp(msg, 7, 1)
    assert out["tr"][0] == "7.1" and out["tr"][1] == "origin-node"
    assert isinstance(out["tr"][2], int)
    # the receiver pops the stamp (the state machine and its WAL see
    # the untraced shape) and records the link span
    causal.set_node("recv-node")
    causal.take(out, "vote")
    assert "tr" not in out
    spans = causal.dump()["spans"]
    assert len(spans) == 1
    ev = spans[0]
    assert ev["n"] == "p2p.recv" and ev["h"] == 7 and ev["r"] == 1
    assert ev["a"]["origin"] == "origin-node"
    assert ev["a"]["sent"] <= ev["t"]


def test_mempool_kind_maps_to_mempool_recv():
    causal.configure("on")
    msg = causal.stamp(dict(_ENVELOPES[5]), 4)
    causal.take(msg, "txs")
    assert causal.dump()["spans"][0]["n"] == "mempool.recv"


def test_span_catalog_enforced_at_record():
    causal.configure("on")
    with pytest.raises(ValueError):
        causal.record("not.a.declared.span", 1)
    # declared names record fine, spans measure a duration
    with causal.span("apply", 3, txs=10):
        time.sleep(0.01)
    ev = causal.dump()["spans"][-1]
    assert ev["n"] == "apply" and ev["d"] >= 5_000_000


@pytest.fixture
def counters_on():
    """`trace_events_dropped_total` counts only while telemetry is on,
    a process-wide switch: a file that ran earlier in this worker may
    have left it off (tests/test_serving_workers.py runs validators in
    process with telemetry off, as its deployment is designed to)."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(was)


def test_causal_ring_cap_and_drop_counter(counters_on):
    causal.configure("on")
    causal.set_capacity(10)
    before = telemetry.value("trace_events_dropped_total") or 0.0
    for i in range(25):
        causal.point("commit", i + 1)
    d = causal.dump()
    assert d["events"] == 10
    # oldest rolled off; the newest height survives
    assert d["spans"][-1]["h"] == 25
    after = telemetry.value("trace_events_dropped_total") or 0.0
    assert after - before == 15


def test_tracer_ring_cap_regression(counters_on):
    """PR 1 Tracer satellite: explicit cap + drop accounting (was a
    silent deque(maxlen) eviction)."""
    t = ttrace.Tracer(capacity=5)
    before = telemetry.value("trace_events_dropped_total") or 0.0
    for i in range(8):
        t.instant(f"e{i}")
    assert len(t.events()) == 5
    assert t.dropped == 3
    assert (telemetry.value("trace_events_dropped_total") or 0.0) \
        - before == 3
    # the survivors are the NEWEST five
    assert [e["name"] for e in t.events()] == \
        [f"e{i}" for i in range(3, 8)]


def test_stall_detector_fires_once_per_episode_and_rearms():
    causal.configure("on")
    h = [5]
    fired = []
    det = causal.StallDetector(lambda: h[0],
                               lambda hh, s: fired.append((hh, s)),
                               window_s=0.15, poll_s=0.03)
    det.start()
    try:
        deadline = time.monotonic() + 3.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fired and fired[0][0] == 5 and fired[0][1] >= 0.15
        n = len(fired)
        time.sleep(0.3)          # still stalled: must NOT refire
        assert len(fired) == n
        h[0] = 6                 # progress re-arms
        time.sleep(0.05)
        deadline = time.monotonic() + 3.0
        while len(fired) <= n and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(fired) == n + 1 and fired[-1][0] == 6
    finally:
        det.stop()
    # the ring carries the flight-recorder markers
    stalls = [e for e in causal.dump()["spans"] if e["n"] == "stall"]
    assert len(stalls) >= 2


# --------------------------------------------------------- merge plane

def _mk_dump(node, spans, rtt=None):
    return {"node": node, "pid": 1, "wall_ns": 0, "enabled": True,
            "capacity": 262144, "events": len(spans),
            "rtt_s": rtt or {}, "spans": spans}


def _recv(origin, sent_ns, recv_ns, h=1):
    return {"n": "p2p.recv", "h": h, "r": 0, "t": recv_ns, "d": 0,
            "a": {"origin": origin, "sent": sent_ns, "kind": "vote"}}


def test_clock_alignment_recovers_synthetic_skew():
    """Node b's clock runs 50 ms ahead; symmetric 2 ms one-way delay.
    The pairwise minimum estimator must recover the offset to well
    under the delay floor."""
    ms = 1_000_000
    skew, delay = 50 * ms, 2 * ms
    a_spans, b_spans = [], []
    for i in range(10):
        t = i * 100 * ms
        jitter = (i % 3) * ms          # asymmetric queueing noise
        # a -> b: sent on a's clock, received on b's (true + skew)
        b_spans.append(_recv("a", t, t + delay + jitter + skew))
        # b -> a: sent on b's clock (true + skew), received on a's
        a_spans.append(_recv("b", t + skew, t + delay + jitter))
    offsets = merge.estimate_offsets(
        [_mk_dump("a", a_spans), _mk_dump("b", b_spans)])
    assert offsets["a"] == 0
    assert abs(offsets["b"] - skew) <= delay
    rtts = merge.pair_rtt_floor_s(
        [_mk_dump("a", a_spans), _mk_dump("b", b_spans)])
    assert abs(rtts["a<->b"] - 2 * delay / 1e9) < 1e-3


def _height_spans(h, t0, off=0):
    """One height's boundary events starting at t0 (ns), shifted by a
    clock offset: begin +0, first part +5ms, full +15ms, prevote quorum
    +25ms, precommit quorum +35ms, apply 35-50ms, fsync 50-60ms."""
    ms = 1_000_000

    def ev(name, at, dur=0, r=0):
        return {"n": name, "h": h, "r": r, "t": t0 + at + off, "d": dur}

    return [
        ev("height.begin", 0),
        ev("part.first", 5 * ms),
        ev("block.full", 15 * ms),
        ev("quorum.prevote", 25 * ms),
        ev("quorum.precommit", 35 * ms),
        ev("apply", 35 * ms, dur=15 * ms),
        ev("wal.fsync", 50 * ms, dur=10 * ms),
        ev("commit", 60 * ms),
    ]


def test_attribution_table_and_coverage():
    ms = 1_000_000
    skew = 40 * ms
    a_spans, b_spans = [], []
    for h in range(1, 6):
        t0 = h * 200 * ms
        a_spans += _height_spans(h, t0)
        # node b sees everything 3 ms later on a skewed clock
        b_spans += _height_spans(h, t0 + 3 * ms, off=skew)
        a_spans.append(_recv("b", t0 + skew, t0 + 2 * ms, h=h))
        b_spans.append(_recv("a", t0, t0 + 2 * ms + skew, h=h))
    dumps = [_mk_dump("a", a_spans), _mk_dump("b", b_spans)]
    rep = merge.attribution(dumps)
    assert rep["heights"] == 5 and rep["heights_skipped"] == 0
    # stages are consecutive boundary deltas: coverage is exact
    assert rep["coverage_mean"] >= 0.99
    s = rep["stages_ms_p50_p95"]
    assert abs(s["first_part"]["p50_ms"] - 5.0) < 2.5
    assert abs(s["full_block"]["p50_ms"] - 10.0) < 2.5
    assert abs(s["apply"]["p50_ms"] - 15.0) < 2.5
    assert abs(s["persist"]["p50_ms"] - 10.0) < 2.5
    assert abs(s["height_wall"]["p50_ms"] - 60.0) < 5.0
    row = rep["per_height"][0]
    assert row["coverage"] >= 0.99


def test_perfetto_merge_one_pid_per_node():
    ms = 1_000_000
    dumps = [_mk_dump("a", _height_spans(1, 10 * ms)),
             _mk_dump("b", _height_spans(1, 13 * ms))]
    doc = merge.to_perfetto(dumps, offsets={"a": 0, "b": 0})
    evs = doc["traceEvents"]
    metas = [e for e in evs if e.get("ph") == "M"]
    assert {m["pid"] for m in metas} == {0, 1}
    body = [e for e in evs if e.get("ph") != "M"]
    assert all(e["ts"] >= 0 for e in body)
    assert any(e["ph"] == "X" and e["name"] == "apply" for e in body)
    # merge_report composes the whole pipeline
    rep = merge.merge_report(dumps)
    assert rep["nodes"] == ["a", "b"]
    assert rep["attribution"]["heights"] == 1


# ------------------------------------------------------- span-name lint

def test_span_catalog_lint_flags_undeclared_names(tmp_path):
    from tendermint_tpu.analysis.checkers import metrics as mcheck
    bad = tmp_path / "bad.py"
    bad.write_text('from tendermint_tpu.telemetry import causal\n'
                   'causal.point("bogus.span", 1)\n'
                   'with causal.span("apply", 2):\n'
                   '    pass\n')
    findings = mcheck.span_findings(str(tmp_path))
    assert len(findings) == 1
    assert "bogus.span" in findings[0].message
    assert findings[0].line == 2
    # the real tree is clean (the same gate scripts/lint.py runs)
    assert mcheck.span_findings() == []


# ------------------------------------------------------- RPC surface

def test_dump_route_and_debug_endpoint():
    from tendermint_tpu.rpc.client import JSONRPCClient
    from tendermint_tpu.rpc.core import RPCEnv, make_server
    causal.configure("on")
    causal.set_node("rpc-node")
    causal.point("commit", 9, txs=3)
    causal.point("commit", 12, txs=1)
    server, _core = make_server(RPCEnv())
    host, port = server.serve("127.0.0.1", 0)
    try:
        c = JSONRPCClient(f"http://{host}:{port}")
        d = c.call("dump_height_timeline")
        assert d["node"] == "rpc-node" and d["enabled"] is True
        assert [e["h"] for e in d["spans"]] == [9, 12]
        # height filter keeps only the asked-for window
        d2 = c.call("dump_height_timeline", min_height=10)
        assert [e["h"] for e in d2["spans"]] == [12]
        # raw GET endpoint serves the same payload, no JSON-RPC envelope
        with urllib.request.urlopen(
                f"http://{host}:{port}/debug/timeline", timeout=10) as r:
            raw = json.loads(r.read())
        assert raw["node"] == "rpc-node"
        assert [e["h"] for e in raw["spans"]] == [9, 12]
    finally:
        server.stop()


# --------------------------------------------------- keepalive RTT

def test_mconn_keepalive_rtt_sample():
    from tendermint_tpu.p2p.conn import ChannelDescriptor, MConnection
    from tendermint_tpu.p2p.conn.mconn import PlainFramedConn
    s1, s2 = socket.socketpair()
    descs = [ChannelDescriptor(0x01, priority=1)]
    m1 = MConnection(PlainFramedConn(s1), descs,
                     on_receive=lambda ch, m: None,
                     ping_interval=0.05, idle_timeout=30.0)
    m2 = MConnection(PlainFramedConn(s2), descs,
                     on_receive=lambda ch, m: None,
                     ping_interval=0.05, idle_timeout=30.0)
    m1.start()
    m2.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                not (m1.rtt_s() > 0 or m2.rtt_s() > 0):
            time.sleep(0.02)
        assert m1.rtt_s() > 0 or m2.rtt_s() > 0
        assert max(m1.rtt_s(), m2.rtt_s()) < 5.0
    finally:
        m1.stop()
        m2.stop()


# ---------------------------------------- cross-node propagation (TCP)

def test_trace_propagation_two_node_tcp_net(tmp_path, monkeypatch):
    """TM_TPU_TRACE=on across a real 2-node TCP net: receive-side link
    spans appear with the sender's origin id and sane (send <= recv +
    slack) clock pairs, consensus spans cover the committed heights,
    and consensus itself is unaffected. (Both in-process nodes share
    the process-global ring and node label, so per-node attribution is
    exercised in the socket bench / merge tests; THIS test proves the
    wire stamps round-trip end to end.)"""
    monkeypatch.setenv("TM_TPU_TRACE", "on")
    from tendermint_tpu.config import test_config as make_test_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import (GenesisDoc, GenesisValidator,
                                      PrivKey)
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)
    keys = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(2)]
    gen = GenesisDoc(chain_id="trace-net", genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519, 10)
                                 for k in keys])
    nodes = []
    for i, k in enumerate(keys):
        cfg = make_test_config(str(tmp_path / f"node{i}"))
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.addr_book_strict = False
        nodes.append(Node(cfg, gen,
                          priv_validator=PrivValidator(LocalSigner(k)),
                          in_memory=True, with_p2p=True))
    ids = {n.switch.node_info.id[:12] for n in nodes}
    try:
        for n in nodes:
            n.start()
        nodes[1].switch.dial_peer(nodes[0].switch.listen_address)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and \
                not all(n.height >= 3 for n in nodes):
            time.sleep(0.05)
        assert all(n.height >= 3 for n in nodes), \
            [n.height for n in nodes]
    finally:
        for n in nodes:
            n.stop()
    spans = causal.dump()["spans"]
    by_name: dict = {}
    for e in spans:
        by_name.setdefault(e["n"], []).append(e)
    # wire stamps arrived and were linked: origin ids are real node ids
    recvs = by_name.get("p2p.recv", [])
    assert recvs, "no receive-side link spans recorded"
    assert {e["a"]["origin"] for e in recvs} <= ids
    assert all(e["a"]["sent"] <= e["t"] + 50_000_000 for e in recvs)
    assert any(e["h"] >= 1 for e in recvs)
    # the consensus timeline covers the committed heights
    for name in ("height.begin", "quorum.prevote", "quorum.precommit",
                 "apply", "wal.fsync", "commit"):
        hs = {e["h"] for e in by_name.get(name, [])}
        assert any(h >= 1 for h in hs), f"missing {name} spans"
    # trace ids keyed the envelopes to real heights: a recv span's
    # height matches a height the cluster actually ran
    run_heights = {e["h"] for e in by_name.get("commit", [])}
    assert {e["h"] for e in recvs if e["h"] > 0} & run_heights
