"""Runtime introspection plane (telemetry/profile.py + queues.py):
profiler on/off neutrality of the hot path, subsystem attribution on a
synthetic busy thread, lock-wait recognition, collapsed-stack caps,
queue gauge correctness under fill/drain, saturation watchdog
fires-once-and-re-arms, weakref pruning, /healthz + /debug/pprof over
HTTP, debug_profile RPC actions, cluster profile merging (the
scripts/profile_merge.py path) and the stall flight recorder's embedded
profile + queue table."""

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

# the operational CLI under test (profile_merge) lives in
# scripts/, which is not a package — importable the way trace_merge's
# own header does it
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))

from tendermint_tpu import telemetry
from tendermint_tpu.telemetry import profile, queues


@pytest.fixture(autouse=True)
def _introspection_reset(monkeypatch):
    """Profiler and observatory are process-global; every test starts
    from the off/empty state and leaves nothing running."""
    monkeypatch.delenv("TM_TPU_PROF", raising=False)
    monkeypatch.delenv("TM_TPU_PROF_HZ", raising=False)
    monkeypatch.delenv("TM_TPU_QUEUE_WATCH", raising=False)
    profile.configure("off")
    queues.configure("on")
    queues.reset()
    yield
    profile.stop()
    p = profile.get()
    if p is not None:
        p.clear()
    profile.configure("off")
    queues.configure("on")
    queues.reset()


# ------------------------------------------------------------- profiler

def _spin_in_ops(stop: threading.Event) -> threading.Thread:
    """A busy thread whose leaf frames live under tendermint_tpu/ops —
    the subsystem the attribution test expects to dominate."""
    from tendermint_tpu.ops import merkle

    def busy():
        data = [b"x%d" % i for i in range(32)]
        while not stop.is_set():
            merkle.root_host(data)

    t = threading.Thread(target=busy, daemon=True, name="tm-prof-busy")
    t.start()
    return t


def test_off_means_no_thread_and_noop_entry_points():
    assert profile.enabled() is False
    assert profile.maybe_start() is None
    assert profile.get() is None or not profile.get().running
    # the unconditional snapshot (healthz/stall embed) is still safe
    snap = profile.snapshot()
    assert snap["running"] is False and snap["samples"] == 0


def test_knob_enables_and_sets_hz(monkeypatch):
    monkeypatch.setenv("TM_TPU_PROF", "on")
    monkeypatch.setenv("TM_TPU_PROF_HZ", "123.0")
    assert profile.enabled() is True
    assert profile.default_hz() == 123.0
    p = profile.maybe_start()
    assert p is not None and p.running and p.hz == 123.0
    profile.stop()
    assert not p.running


def test_hot_path_bytes_identical_with_profiler_running():
    """The profiler only OBSERVES: block serialization + part-set
    roots under active sampling are byte-for-byte the unprofiled
    ones."""
    from tendermint_tpu.types.block import Block, Data, Header

    def build():
        h = Header(chain_id="prof-test", height=3, time_ns=1,
                   validators_hash=b"\x02" * 32)
        blk = Block(h, Data([b"k=v", b"a=b"]))
        blk.fill_header()
        return blk

    ref = build()
    before = (ref.to_bytes(), ref.make_part_set(64).header().hash)
    p = profile.start(hz=500)
    assert p.running
    try:
        for _ in range(25):
            blk = build()
            during = (blk.to_bytes(),
                      blk.make_part_set(64).header().hash)
            assert during == before
    finally:
        profile.stop()


def test_subsystem_attribution_on_busy_thread():
    """The synthetic busy thread's samples land under its OWN thread
    label with an ops/native subsystem (root_host dispatches into
    native/ when the C plane is available, ops/ otherwise — the split
    itself is the attribution working). Asserted per-thread, not on
    global shares: in a full-suite run other modules' leftover
    threads legitimately share the core."""
    def our_samples():
        return sum(telemetry.value(
            "prof_samples_total",
            {"subsystem": s, "thread": "tm-prof-busy"}) or 0
            for s in ("ops", "native"))

    base = our_samples()
    stop = threading.Event()
    t = _spin_in_ops(stop)
    p = profile.start(hz=300)
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            time.sleep(0.05)
            if our_samples() - base >= 5:
                break
        else:
            pytest.fail(
                f"busy thread never attributed: "
                f"{p.subsystem_shares()} (ours: {our_samples() - base})")
    finally:
        profile.stop()
        stop.set()
        t.join(timeout=2.0)
    # shares are a distribution over busy samples
    assert abs(sum(p.subsystem_shares().values()) - 1.0) < 0.01
    # and the busy tree shows up in the distribution at all
    shares = p.subsystem_shares()
    assert shares.get("ops", 0.0) + shares.get("native", 0.0) > 0.0


def test_lock_wait_recognized_not_counted_busy():
    """A thread parked in Condition.wait (a threading.py leaf frame) is
    a lock-wait sample: excluded from busy shares, charged to
    tm_prof_lock_wait_samples_total, flagged in the collapsed stack."""
    cond = threading.Condition()
    stop = threading.Event()

    def parked():
        with cond:
            while not stop.is_set():
                cond.wait(timeout=5.0)

    t = threading.Thread(target=parked, daemon=True,
                         name="tm-prof-parked")
    t.start()
    p = profile.start(hz=300)
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                p.snapshot()["wait_samples"] < 5:
            time.sleep(0.05)
    finally:
        profile.stop()
        stop.set()
        with cond:
            cond.notify_all()
        t.join(timeout=2.0)
    snap = p.snapshot()
    assert snap["wait_samples"] >= 5
    assert "[lock_wait]" in p.collapsed()
    # parked time is not CPU share: busy totals reconcile without it
    assert snap["samples"] == sum(snap["subsystems"].values())
    assert sum(snap["lock_wait"].values()) == snap["wait_samples"]


def test_collapsed_format_and_stack_cap():
    p = profile.SamplingProfiler(hz=100, max_stacks=2)
    # synthesize records via the internal recorder on real frames
    import sys
    frame = sys._current_frames()[threading.get_ident()]
    for _ in range(5):
        p._record(frame, "t-a")
    lines = [ln for ln in p.collapsed().splitlines() if ln]
    assert all(" " in ln and ";" in ln for ln in lines)
    # every line is "stack N" with integer N
    for ln in lines:
        stack, n = ln.rsplit(" ", 1)
        assert int(n) >= 1 and stack.startswith("t-a;")
    # overflow past max_stacks aggregates under (truncated)
    snap_before = p.snapshot()["stacks"]
    assert snap_before <= 2
    # force two distinct stacks then a third: the third truncates

    def one_deeper():
        return sys._current_frames()[threading.get_ident()]

    p._record(one_deeper(), "t-b")
    p._record(frame, "t-c")
    assert p.snapshot()["stacks_dropped"] >= 1
    assert any("(truncated)" in ln for ln in p.collapsed().splitlines())


def test_thread_name_normalization():
    assert profile._normalize_thread("Thread-12 (worker)") == "Thread"
    assert profile._normalize_thread("tm-verify-fetch-3") == \
        "tm-verify-fetch"
    assert profile._normalize_thread("mconn-send") == "mconn-send"
    assert profile._normalize_thread("rpc-http") == "rpc-http"


# ------------------------------------------------------ queue observatory

class _FakeQueue:
    def __init__(self):
        self.items = []


def test_queue_gauges_under_fill_and_drain():
    q = _FakeQueue()
    queues.register("test.fill", q, depth=lambda o: len(o.items),
                    capacity=8)
    telemetry.set_enabled(True)
    try:
        q.items = [1, 2, 3]
        queues.poll()
        assert telemetry.value("queue_depth", {"queue": "test.fill"}) == 3
        assert telemetry.value("queue_capacity",
                               {"queue": "test.fill"}) == 8
        assert telemetry.value("queue_high_water",
                               {"queue": "test.fill"}) == 3
        assert telemetry.value("queue_saturation",
                               {"queue": "test.fill"}) == pytest.approx(
            3 / 8)
        q.items = []
        queues.poll()
        assert telemetry.value("queue_depth",
                               {"queue": "test.fill"}) == 0
        # high water survives the drain
        assert telemetry.value("queue_high_water",
                               {"queue": "test.fill"}) == 3
        t = queues.table()["test.fill"]
        assert t["high_water"] == 3 and t["depth"] == 0
        assert t["instances"] == 1 and t["wait_s"] == 0.0
    finally:
        telemetry.set_enabled(True)


def test_fullest_instance_wins_and_weakref_prunes():
    a, b = _FakeQueue(), _FakeQueue()
    queues.register("test.multi", a, depth=lambda o: len(o.items),
                    capacity=10)
    queues.register("test.multi", b, depth=lambda o: len(o.items),
                    capacity=10)
    a.items, b.items = [1], [1, 2, 3, 4, 5]
    queues.poll()
    t = queues.table()["test.multi"]
    assert t["depth"] == 5 and t["instances"] == 2
    del b
    import gc
    gc.collect()
    queues.poll()
    t = queues.table()["test.multi"]
    assert t["instances"] == 1 and t["depth"] == 1


def test_watchdog_fires_once_and_rearms():
    q = _FakeQueue()
    queues.register("test.sat", q, depth=lambda o: len(o.items),
                    capacity=10)
    fired = []
    queues.on_saturation(lambda k, s, d: fired.append((k, d)))
    q.items = list(range(9))          # 90% > threshold
    queues.poll()
    queues.poll()                     # still saturated: same episode
    queues.poll()
    assert fired == [("test.sat", 9)]
    assert queues.saturated() == ["test.sat"]
    q.items = [1]                     # drains: re-arm
    queues.poll()
    assert queues.saturated() == []
    q.items = list(range(10))         # second episode
    queues.poll()
    assert fired == [("test.sat", 9), ("test.sat", 10)]
    assert queues.table()["test.sat"]["events"] == 2


def test_watch_thread_and_off_knob(monkeypatch):
    # on: the watcher thread runs sweeps without explicit poll()
    q = _FakeQueue()
    queues.register("test.watch", q, depth=lambda o: len(o.items),
                    capacity=4)
    monkeypatch.setenv("TM_TPU_QUEUE_WATCH", "0.02")
    assert queues.ensure_watch() is True
    q.items = [1, 2]
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline:
        if queues.table().get("test.watch", {}).get("depth") == 2:
            break
        time.sleep(0.02)
    else:
        pytest.fail("watcher never swept")
    queues.stop_watch()
    # off: registration short-circuits to the no-op probe
    monkeypatch.setenv("TM_TPU_QUEUE_WATCH", "off")
    probe = queues.register("test.noop", q,
                            depth=lambda o: len(o.items), capacity=4)
    assert probe is queues._NOOP_PROBE
    assert queues.ensure_watch() is False


def test_real_owners_register_into_catalog():
    """A wired owner (EventBus subscription) lands in the catalog with
    live depths; unsubscribe removes it. The verifier keeps no queue."""
    from tendermint_tpu.types.events import EventBus
    bus = EventBus()
    sub = bus.subscribe("obs-test", "tm.event = 'Tx'", capacity=4)
    queues.poll()
    t = queues.table()["event.subscriber"]
    assert t["instances"] >= 1 and t["capacity"] == 4
    bus.publish("Tx", {"n": 1}, {"tx.hash": "AA"})
    queues.poll()
    assert queues.table()["event.subscriber"]["depth"] == 1
    assert sub.qsize() == 1
    bus.unsubscribe_all("obs-test")
    queues.poll()
    assert queues.table()["event.subscriber"]["instances"] == 0

    from tendermint_tpu.models.verifier import BatchVerifier
    assert BatchVerifier("python").verify([]).tolist() == []
    queues.poll()
    assert not [k for k in queues.table() if k.startswith("verifier")]


# --------------------------------------------------------- RPC surface

def test_healthz_and_pprof_over_http(monkeypatch):
    from tendermint_tpu.rpc.client import JSONRPCClient
    from tendermint_tpu.rpc.core import RPCEnv, make_server
    q = _FakeQueue()
    queues.register("test.http", q, depth=lambda o: len(o.items),
                    capacity=10)
    server, _core = make_server(RPCEnv())
    host, port = server.serve("127.0.0.1", 0)
    try:
        # healthy: nothing saturated, no stall detector, profiler off
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["ok"] is True
        assert doc["queues"]["saturated"] == []
        assert "test.http" in doc["queues"]["table"]
        assert doc["profile"]["running"] is False
        # saturate: the verdict flips
        q.items = list(range(10))
        queues.poll()
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["ok"] is False
        assert doc["queues"]["saturated"] == ["test.http"]

        # debug_profile RPC: start -> dump -> stop
        c = JSONRPCClient(f"http://{host}:{port}")
        st = c.call("debug_profile", action="status")
        assert st["running"] is False
        c.call("debug_profile", action="start", hz=200)
        stop = threading.Event()
        t = _spin_in_ops(stop)
        deadline = time.monotonic() + 5.0
        dump = {}
        while time.monotonic() < deadline:
            time.sleep(0.05)
            dump = c.call("debug_profile", action="dump")
            if dump["samples"] >= 10:
                break
        stop.set()
        t.join(timeout=2.0)
        assert dump["samples"] >= 10 and dump["collapsed"]
        # raw pprof path serves the same collapsed text, text/plain
        with urllib.request.urlopen(
                f"http://{host}:{port}/debug/pprof", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert text.strip() and ";" in text
        out = c.call("debug_profile", action="stop")
        assert out["running"] is False
        assert c.call("debug_profile", action="status")[
            "running"] is False
    finally:
        server.stop()
        profile.stop()


# ------------------------------------------------------------- merging

def _synthetic_dump(node: str, subsys: dict, waits: dict,
                    stacks: dict) -> dict:
    return {
        "node": node,
        "samples": sum(subsys.values()),
        "wait_samples": sum(waits.values()),
        "subsystems": subsys, "lock_wait": waits,
        "shares": {}, "collapsed": "\n".join(
            f"{k} {v}" for k, v in stacks.items()),
    }


def test_profile_merge_two_nodes():
    d1 = _synthetic_dump("aaa", {"consensus": 60, "p2p": 40},
                         {"consensus": 10},
                         {"main;a.f;b.g": 60, "main;a.f;c.h": 40})
    d2 = _synthetic_dump("bbb", {"consensus": 20, "verifier": 80},
                         {"p2p": 5},
                         {"main;a.f;b.g": 100})
    merged = profile.merge_dumps([d1, d2])
    assert merged["nodes"] == ["aaa", "bbb"]
    assert merged["samples"] == 200 and merged["wait_samples"] == 15
    assert merged["subsystems"] == {"consensus": 80, "p2p": 40,
                                    "verifier": 80}
    assert merged["shares"]["consensus"] == pytest.approx(0.4)
    assert abs(sum(merged["shares"].values()) - 1.0) < 0.01
    # per-node trees re-rooted so one flamegraph holds the cluster
    lines = merged["collapsed"].splitlines()
    assert "node:aaa;main;a.f;b.g 60" in lines
    assert "node:bbb;main;a.f;b.g 100" in lines


def test_profile_merge_script_on_files(tmp_path):
    import profile_merge
    d1 = _synthetic_dump("n0", {"consensus": 10}, {}, {"m.f;m.g": 10})
    d2 = _synthetic_dump("n1", {"p2p": 30}, {}, {"m.f;m.h": 30})
    f1, f2 = tmp_path / "d0.json", tmp_path / "d1.json"
    f1.write_text(json.dumps(d1))
    f2.write_text(json.dumps(d2))
    out = tmp_path / "merged.collapsed"
    report = tmp_path / "report.json"
    rc = profile_merge.main(["--files", str(f1), str(f2),
                             "--out", str(out),
                             "--report", str(report)])
    assert rc == 0
    text = out.read_text()
    assert "node:n0;" in text and "node:n1;" in text
    rep = json.loads(report.read_text())
    assert rep["samples_busy"] == 40
    assert rep["shares"]["p2p"] == pytest.approx(0.75)


# ------------------------------------------------- stall flight recorder

def test_stall_dump_embeds_profile_and_queue_table(tmp_path):
    """Satellite: a stall capture is self-diagnosing — the flight
    recorder document carries the profiler snapshot and the queue
    high-water table alongside the causal timeline."""
    from tendermint_tpu.telemetry import causal

    q = _FakeQueue()
    queues.register("test.stall", q, depth=lambda o: len(o.items),
                    capacity=5)
    q.items = [1, 2, 3, 4]
    queues.poll()
    p = profile.start(hz=100)
    time.sleep(0.05)

    # the node's _on_stall path, driven without a full Node: replicate
    # its doc assembly through the same module entry points
    doc = {"height": 7, "stalled_s": 1.5,
           "timeline": causal.dump(),
           "profile": profile.snapshot(),
           "queues": queues.table()}
    profile.stop()
    path = tmp_path / "tm_stall_h7.json"
    path.write_text(json.dumps(doc))
    back = json.loads(path.read_text())
    assert back["queues"]["test.stall"]["high_water"] == 4
    assert back["profile"]["running"] in (True, False)
    assert "collapsed" in back["profile"]
    assert back["timeline"]["events"] >= 0


def test_node_on_stall_writes_self_diagnosing_dump(tmp_path,
                                                   monkeypatch):
    """The REAL Node._on_stall: build an in-memory node, invoke the
    stall callback directly, and assert the dump file embeds profile +
    queues next to the timeline."""
    from tendermint_tpu.config import test_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import (GenesisDoc, GenesisValidator,
                                      PrivKey)
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)

    key = PrivKey.generate(b"\x0b" * 32)
    gen = GenesisDoc(chain_id="stall-test", genesis_time_ns=1,
                     validators=[GenesisValidator(key.pubkey.ed25519,
                                                  10)])
    cfg = test_config("")
    monkeypatch.setattr("tempfile.gettempdir", lambda: str(tmp_path))
    node = Node(cfg, gen, priv_validator=PrivValidator(LocalSigner(key)),
                in_memory=True)
    try:
        node._on_stall(3, 2.0)
    finally:
        node.stop()
    dumps = list(tmp_path.glob("tm_stall_h3_*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert "profile" in doc and "queues" in doc
    assert doc["profile"]["running"] is False  # knob off: observed only
    assert isinstance(doc["queues"], dict)
    assert "consensus" in doc


# ------------------------------------------------------------- catalog

def test_metrics_catalog_includes_prof_and_queue():
    from tendermint_tpu.analysis.checkers import metrics as mcheck
    assert "prof" in mcheck.KNOWN_SUBSYSTEMS
    assert "queue" in mcheck.KNOWN_SUBSYSTEMS
    assert "tendermint_tpu.telemetry.profile" in \
        mcheck.INSTRUMENTED_MODULES
    assert "tendermint_tpu.telemetry.queues" in \
        mcheck.INSTRUMENTED_MODULES
    findings = mcheck.run()
    assert findings == [], [f.message for f in findings]
