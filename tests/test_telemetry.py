"""Telemetry subsystem: registry semantics, exposition format, no-op
mode, tracing, the /metrics HTTP route, and the check_metrics lint."""

import http.client
import json
import os
import subprocess
import sys

import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.telemetry.registry import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- registry --

def test_counter_basics():
    r = Registry()
    c = r.counter("sub_hits_total", "hits")
    c.inc()
    c.inc(2.5)
    assert r.value("sub_hits_total") == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_labels_independent():
    r = Registry()
    c = r.counter("sub_ops_total", "ops", ("kind",))
    c.labels("a").inc()
    c.labels(kind="b").inc(4)
    c.labels("a").inc()
    assert r.value("sub_ops_total", {"kind": "a"}) == 2
    assert r.value("sub_ops_total", {"kind": "b"}) == 4
    assert r.value("sub_ops_total", {"kind": "never"}) is None
    # a labelled family rejects implicit-child ops and wrong labels
    with pytest.raises(ValueError):
        c.inc()
    with pytest.raises(ValueError):
        c.labels("a", "b")
    with pytest.raises(ValueError):
        c.labels(wrong="x")


def test_gauge_set_inc_dec():
    r = Registry()
    g = r.gauge("sub_depth", "depth")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert r.value("sub_depth") == 13


def test_histogram_bucket_semantics():
    r = Registry()
    h = r.histogram("sub_len", "lengths", buckets=(1, 2, 4, 8))
    for v in (0.5, 1, 2, 3, 8, 9):
        h.observe(v)
    out = r.value("sub_len")
    assert out["count"] == 6
    assert out["sum"] == 23.5
    # le buckets are INCLUSIVE upper bounds, cumulative
    assert out["buckets"][1.0] == 2      # 0.5, 1
    assert out["buckets"][2.0] == 3      # + 2
    assert out["buckets"][4.0] == 4      # + 3
    assert out["buckets"][8.0] == 5      # + 8
    assert out["buckets"][float("inf")] == 6  # + 9


def test_duplicate_registration():
    r = Registry()
    a = r.counter("sub_x_total", "x")
    assert r.counter("sub_x_total", "x") is a       # idempotent
    with pytest.raises(ValueError):
        r.gauge("sub_x_total", "x")                 # kind mismatch
    with pytest.raises(ValueError):
        r.counter("sub_x_total", "x", ("l",))       # label mismatch
    r.histogram("sub_h", "h", buckets=(1, 2))
    with pytest.raises(ValueError):
        r.histogram("sub_h", "h", buckets=(1, 2, 3))  # bucket mismatch


def test_name_validation():
    r = Registry()
    for bad in ("", "1x", "Has-Dash", "UPPER", "sp ace"):
        with pytest.raises(ValueError):
            r.counter(bad, "bad")
    with pytest.raises(ValueError):
        r.counter("sub_ok_total", "x", ("0bad",))


def test_noop_mode_records_nothing():
    r = Registry()
    c = r.counter("sub_n_total", "n")
    h = r.histogram("sub_nh", "nh", buckets=(1,))
    lc = r.counter("sub_nl_total", "nl", ("k",))
    c.inc()
    telemetry.set_enabled(False)
    try:
        c.inc(100)
        h.observe(5)
        lc.labels("a").inc()          # returns the shared no-op child
        assert not telemetry.enabled()
    finally:
        telemetry.set_enabled(True)
    assert r.value("sub_n_total") == 1
    assert r.value("sub_nh")["count"] == 0
    assert r.value("sub_nl_total", {"k": "a"}) is None


def test_reset_zeroes_but_keeps_families():
    r = Registry()
    c = r.counter("sub_r_total", "r", ("k",))
    c.labels("a").inc(7)
    r.reset()
    assert r.value("sub_r_total", {"k": "a"}) == 0
    assert "sub_r_total" in r.names()


# ----------------------------------------------------------- exposition --

def test_exposition_golden():
    r = Registry()
    r.counter("app_reqs_total", "Requests served", ("code",))\
        .labels(code="200").inc(3)
    r.gauge("app_depth", "Queue depth").set(2.5)
    h = r.histogram("app_lat_seconds", "Latency", buckets=(0.1, 1))
    h.observe(0.05)
    h.observe(0.5)
    assert r.expose(namespace="ns") == (
        "# HELP ns_app_depth Queue depth\n"
        "# TYPE ns_app_depth gauge\n"
        "ns_app_depth 2.5\n"
        "# HELP ns_app_lat_seconds Latency\n"
        "# TYPE ns_app_lat_seconds histogram\n"
        'ns_app_lat_seconds_bucket{le="0.1"} 1\n'
        'ns_app_lat_seconds_bucket{le="1"} 2\n'
        'ns_app_lat_seconds_bucket{le="+Inf"} 2\n'
        "ns_app_lat_seconds_sum 0.55\n"
        "ns_app_lat_seconds_count 2\n"
        "# HELP ns_app_reqs_total Requests served\n"
        "# TYPE ns_app_reqs_total counter\n"
        'ns_app_reqs_total{code="200"} 3\n')


def test_exposition_escaping():
    r = Registry()
    r.counter("sub_esc_total", 'help with \\ and\nnewline', ("v",))\
        .labels(v='quo"te\\back\nline').inc()
    text = r.expose(namespace="t")
    assert r'# HELP t_sub_esc_total help with \\ and\nnewline' in text
    assert 't_sub_esc_total{v="quo\\"te\\\\back\\nline"} 1' in text


def test_labelless_family_exposes_header_and_zero():
    r = Registry()
    r.counter("sub_zero_total", "never incremented")
    text = r.expose(namespace="tm")
    assert "# TYPE tm_sub_zero_total counter" in text
    assert "tm_sub_zero_total 0" in text


# ------------------------------------------------------------- tracing --

def test_tracer_span_and_instant():
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer()
    with t.span("work", height=3):
        pass
    t.instant("mark", round=1)
    t.complete("step", 0.5, 0.75, step="PROPOSE")
    evs = t.events()
    assert [e["ph"] for e in evs] == ["X", "i", "X"]
    assert evs[0]["name"] == "work" and evs[0]["args"] == {"height": 3}
    assert evs[0]["dur"] >= 0
    assert evs[2]["dur"] == pytest.approx(0.25e6)
    ct = t.chrome_trace()
    assert ct["traceEvents"] == evs


def test_tracer_dump_and_ring(tmp_path):
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer(capacity=4)
    for i in range(10):
        t.instant(f"e{i}")
    assert len(t.events()) == 4  # ring evicts oldest
    assert t.events()[0]["name"] == "e6"
    p = t.dump(str(tmp_path / "trace.json"))
    with open(p) as f:
        obj = json.load(f)
    assert len(obj["traceEvents"]) == 4
    assert obj["displayTimeUnit"] == "ms"


def test_tracer_disabled_is_noop():
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer()
    telemetry.set_enabled(False)
    try:
        with t.span("x"):
            pass
        t.instant("y")
    finally:
        telemetry.set_enabled(True)
    assert t.events() == []


def test_span_parent_follows_nesting_per_thread():
    import threading
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer()
    with t.span("outer") as outer:
        with t.span("inner") as inner:
            pass
        t.complete("closed", 0.1, 0.2)

        def elsewhere():
            with t.span("other"):
                pass
        th = threading.Thread(target=elsewhere)
        th.start()
        th.join()
    with t.span("after"):
        pass
    by = {e["name"]: e for e in t.events()}
    assert by["outer"]["parent"] == 0
    assert by["inner"]["parent"] == by["outer"]["id"] == outer.id
    assert inner.id == by["inner"]["id"]
    # complete() takes the innermost span open on the calling thread
    assert by["closed"]["parent"] == outer.id
    # another thread's span is not a child of what is open here
    assert by["other"]["parent"] == 0
    assert by["other"]["tid"] != by["outer"]["tid"]
    # the stack unwinds: nothing is left open
    assert by["after"]["parent"] == 0
    ids = [e["id"] for e in t.events()]
    assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)


def test_span_cause_crosses_a_thread_and_req_is_carried():
    import threading
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer()
    with t.span("dispatch", req=41) as d:
        with t.span("prep"):                # takes its parent's req
            pass
        with t.span("own", req=7):
            pass
        cause, req = d.id, d.req

    def resolver():
        with t.span("fetch", req=req, cause=cause):
            pass
        t.complete("inflight", 1.0, 2.0, req=req, cause=cause)
        t.instant("mark", req=req)
    th = threading.Thread(target=resolver)
    th.start()
    th.join()
    by = {e["name"]: e for e in t.events()}
    assert by["fetch"]["cause"] == by["dispatch"]["id"]
    assert by["inflight"]["cause"] == by["dispatch"]["id"]
    assert by["fetch"]["parent"] == 0
    assert {by[n]["req"] for n in
            ("dispatch", "prep", "fetch", "inflight", "mark")} == {41}
    assert by["own"]["req"] == 7
    assert "cause" not in by["prep"]


def test_spans_from_many_threads_keep_their_own_nesting():
    """More threads than cores and a short switch interval: ids stay
    unique, no event is lost, and a span's parent is always the span
    its OWN thread had open."""
    import sys
    import threading
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer(capacity=1 << 16)
    n_threads, n_rounds = 16, 200

    def work(k):
        for i in range(n_rounds):
            with t.span("outer", req=(k, i)):
                with t.span("inner"):
                    pass
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60.0)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    evs = t.events()
    assert len(evs) == 2 * n_threads * n_rounds and t.dropped == 0
    by_id = {e["id"]: e for e in evs}
    assert len(by_id) == len(evs)
    inners = [e for e in evs if e["name"] == "inner"]
    assert all(by_id[e["parent"]]["name"] == "outer" and
               by_id[e["parent"]]["tid"] == e["tid"] and
               by_id[e["parent"]]["req"] == e["req"] for e in inners)
    assert all(e["parent"] == 0 for e in evs if e["name"] == "outer")


def test_span_off_takes_no_id_and_touches_no_thread_local():
    from tendermint_tpu.telemetry import trace as ttrace
    t = ttrace.Tracer()
    with t.span("warm"):            # this thread's stack now exists
        pass
    t.clear()
    stack = ttrace._open_spans()
    telemetry.set_enabled(False)
    try:
        before = next(ttrace._ids)
        sp = t.span("x", req=1)
        assert sp is ttrace._NULL_SPAN and sp is t.span("y")
        with sp as inside:
            assert stack == [] and inside.id == 0
            assert inside.req is None
        t.complete("z", 0.0, 1.0, req=1)
        t.instant("i", req=1)
        assert next(ttrace._ids) == before + 1
    finally:
        telemetry.set_enabled(True)
    assert t.events() == []


def test_tracer_between_clips_by_window_and_counts_window_drops():
    import time
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer(capacity=4)
    base = time.perf_counter()
    t.complete("a", base + 1.0, base + 2.0, req=1)
    t.complete("a", base + 3.0, base + 4.0, req=2, cause=9)
    t.complete("b", base + 3.0, base + 3.5)
    rows, dropped = t.between("a", base + 1.5, base + 3.2)
    assert dropped == 0
    assert [(round(r["start"] - base, 6), round(r["end"] - base, 6),
             r["req"], r["cause"]) for r in rows] == \
        [(1.0, 2.0, 1, None), (3.0, 4.0, 2, 9)]
    assert all(r["id"] and r["tid"] for r in rows)
    assert t.between("a", base + 2.5, base + 2.9) == ([], 0)
    # the ring sheds its oldest: a window that starts after the
    # displaced events ended has lost nothing, an earlier one has
    t.complete("a", base + 5.0, base + 6.0)
    t.complete("a", base + 7.0, base + 8.0)     # displaces a[1..2]
    assert t.dropped == 1
    assert t.between("a", base + 2.5, base + 9.0)[1] == 0
    rows, dropped = t.between("a", base + 0.5, base + 9.0)
    assert dropped == 1 and len(rows) == 3
    # an emptied ring holds nothing against a later window; the count
    # over the tracer's life stays
    t.clear()
    assert t.between("a", base + 0.5, base + 9.0) == ([], 0)
    assert t.dropped == 1


def test_recording_keeps_no_object_per_event():
    """The ring is columns allocated once: a span whose `req` is an int
    and whose args are none or one int leaves no object behind in the
    heap, among those of the work it times (kept as dicts, the events of a traced fast-sync
    moved a millisecond a block between its legs)."""
    import sys
    from tendermint_tpu.telemetry.trace import Tracer
    t = Tracer(capacity=1 << 12)
    n = 2000

    def record(base):
        for i in range(n):
            with t.span("apply.exec", req=base + i):
                pass
            t.complete("sync.store", 0.0, 1.0, req=base + i, bytes=3 * i)
    record(10 ** 6)                 # the thread's stack, the code's caches
    before = sys.getallocatedblocks()
    record(2 * 10 ** 6)
    assert sys.getallocatedblocks() - before < n // 20
    evs = t.events()
    assert len(evs) == 1 << 12 and t.dropped == 4 * n - (1 << 12)
    assert evs[-1]["req"] == 2 * 10 ** 6 + n - 1
    assert evs[-1]["args"] == {"bytes": 3 * (n - 1)} and "args" not in evs[-2]
    # what is no int, and other args, are kept as they came
    t.complete("sync.store", 0.0, 1.0, req=("w", 3), bytes=7, kernel="k")
    t.complete("sync.store", 0.0, 1.0, ok=True)
    assert t.events()[-2]["req"] == ("w", 3)
    assert t.events()[-2]["args"] == {"bytes": 7, "kernel": "k"}
    assert t.events()[-1]["args"] == {"ok": True} and \
        "req" not in t.events()[-1]


def test_span_catalogue_names_every_consensus_step_and_layer():
    from tendermint_tpu.consensus.rstate import Step
    from tendermint_tpu.telemetry.trace import SPANS
    assert {f"cs:{s.name}" for s in Step} <= set(SPANS)
    assert all(isinstance(v, str) and v for v in SPANS.values())


def test_tracer_catalogue_lint_flags_undeclared_names(tmp_path):
    from tendermint_tpu.analysis.checkers import metrics as mcheck
    bad = tmp_path / "bad.py"
    bad.write_text('from tendermint_tpu import telemetry\n'
                   'from tendermint_tpu.telemetry import trace\n'
                   'with trace.span("verify.bogus", req=1):\n'
                   '    pass\n'
                   'with trace.span("verify.prep"):\n'
                   '    pass\n'
                   'trace.complete("cs:SIDEWAYS", 0.0, 1.0)\n'
                   'telemetry.instant("cs:timeout", req=3)\n'
                   'telemetry.TRACER.instant("made.up")\n')
    findings = mcheck.span_findings(str(tmp_path))
    assert [(f.line, f.message.split("'")[1]) for f in findings] == \
        [(3, "verify.bogus"), (7, "cs:SIDEWAYS"), (9, "made.up")]
    assert all("telemetry.trace.SPANS" in f.message for f in findings)
    assert mcheck.span_findings() == []


def test_span_lands_in_the_profiler_trace_as_tm_name(tmp_path):
    """On the device's clock: a span inside a profiler session is in
    the profiler's own trace as `tm:<name>`, whoever started it."""
    import glob
    import jax
    import jax.numpy as jnp
    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("this jax has no ProfileData")
    from tendermint_tpu.telemetry import trace as ttrace
    t = ttrace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("verify.enqueue", req=3, kernel="test"):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert paths, "the profiler wrote no xplane"
    names = {ev.name for p in paths
             for plane in ProfileData.from_file(p).planes
             for line in plane.lines for ev in line.events}
    assert ttrace.ANNOTATION_PREFIX + "verify.enqueue" in names
    assert t.events()[0]["req"] == 3


def test_dispatch_spans_share_a_request_and_name_their_cause():
    """The verifier's spans through a real device-path dispatch (jnp
    kernels on this backend): one `req` for the dispatch's spans,
    nesting on the dispatching thread, `cause` on the resolver's."""
    import threading
    from tendermint_tpu.models.verifier import BatchVerifier
    from tendermint_tpu.types import PrivKey
    priv = PrivKey.generate(b"\x05" * 32)
    items = [(priv.pubkey.ed25519, b"m%d" % i, priv.sign(b"m%d" % i))
             for i in range(8)]
    v = BatchVerifier("jax", mesh="off")
    telemetry.TRACER.clear()
    h2d0 = telemetry.value("verifier_h2d_bytes_total") or 0.0
    resolve = v.verify_async(items)
    out = []
    th = threading.Thread(target=lambda: out.append(resolve()))
    th.start()
    th.join()
    assert out[0].all()
    evs = [e for e in telemetry.TRACER.events()
           if e["name"].startswith("verify.")]
    by = {e["name"]: e for e in evs}
    assert set(by) == {"verify.dispatch", "verify.prep", "verify.enqueue",
                       "verify.fetch", "verify.inflight"}
    disp = by["verify.dispatch"]
    assert disp["args"]["n"] == 8 and disp["args"]["backend"] == "jax"
    assert {e["req"] for e in evs} == {disp["req"]}
    assert by["verify.prep"]["parent"] == disp["id"]
    assert by["verify.enqueue"]["parent"] == disp["id"]
    assert by["verify.enqueue"]["args"]["kernel"] == "jnp_full"
    for name in ("verify.fetch", "verify.inflight"):
        assert by[name]["cause"] == disp["id"]
        assert by[name]["tid"] != disp["tid"]
    # in flight from the first enqueue's end to the fetch's end
    infl, enq, fetch = (by[n] for n in ("verify.inflight", "verify.enqueue",
                                       "verify.fetch"))
    assert infl["ts"] >= enq["ts"] + enq["dur"] - 1.0
    assert abs(infl["ts"] + infl["dur"] -
               (fetch["ts"] + fetch["dur"])) < 5e3
    # 4 arrays of 8 padded rows x 32 bytes left the host
    assert (telemetry.value("verifier_h2d_bytes_total") or 0.0) - h2d0 \
        == 4 * 8 * 32


def test_a_timeout_that_moves_the_state_leaves_one_instant():
    """One validator of four, alone and not the proposer: its propose
    timeout fires and moves it to prevote, which leaves a `cs:timeout`
    instant naming step, height and node; a stale tock leaves none."""
    from tendermint_tpu.consensus import Step
    from tendermint_tpu.consensus.ticker import TimeoutInfo
    from tests.test_consensus import make_net
    nodes, _ = make_net(4)
    proposer = nodes[0].rs.validators.proposer().address
    cs = next(n for n in nodes if n.priv_validator.address != proposer)
    cs.start()
    cs.ticker.fire_next()           # the commit timeout: round 0 opens
    assert cs.rs.step == Step.PROPOSE

    def timeouts():
        return [e for e in telemetry.TRACER.events()
                if e["name"] == "cs:timeout" and
                e["args"]["node"] == cs._trace_node]
    telemetry.TRACER.clear()
    cs._handle_timeout(TimeoutInfo(0.0, 1, 0, Step.NEW_HEIGHT))    # stale
    assert timeouts() == []
    cs.ticker.fire_next()
    assert cs.rs.step >= Step.PREVOTE
    (ev,) = timeouts()
    assert ev["ph"] == "i" and ev["req"] == 1
    assert ev["args"] == {"step": "PROPOSE", "round": 0,
                          "node": cs._trace_node}
    cs._handle_timeout(TimeoutInfo(0.0, 1, 0, Step.PROPOSE))       # stale
    assert len(timeouts()) == 1


# ------------------------------------------------- instrumented modules --

def _small_commit():
    from tendermint_tpu.types import (PrivKey, Validator, ValidatorSet)
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.models.verifier import BatchVerifier
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(4)]
    vs = ValidatorSet([Validator(p.pubkey.ed25519, 10) for p in privs])
    by_addr = {p.pubkey.address: p for p in privs}
    bid = BlockID(b"b" * 32, PartSetHeader(1, b"p" * 32))
    pyv = BatchVerifier("python")
    vset = VoteSet("telemetry-chain", 1, 0, VoteType.PRECOMMIT, vs,
                   verifier=pyv)
    for i, val in enumerate(vs.validators):
        v = Vote(val.address, i, 1, 0, 1000, VoteType.PRECOMMIT, bid)
        v.signature = by_addr[val.address].sign(
            v.sign_bytes("telemetry-chain"))
        vset.add_vote(v)
    return vs, bid, vset.make_commit(), pyv


def test_verifier_metrics_after_verify_commit():
    vs, bid, commit, pyv = _small_commit()
    before = telemetry.value("verifier_sigs_total",
                             {"backend": "python"}) or 0
    vs.verify_commit("telemetry-chain", bid, 1, commit, verifier=pyv)
    after = telemetry.value("verifier_sigs_total", {"backend": "python"})
    assert after >= before + 4
    assert telemetry.value("verifier_batch_size")["count"] > 0
    assert telemetry.value("verifier_dispatch_seconds",
                           {"backend": "python"})["count"] > 0


def test_metrics_route_serves_prometheus_text():
    """Acceptance shape: /metrics serves valid exposition including the
    verifier families after a verify_commit, plus the consensus round
    duration family (registered at import)."""
    import tendermint_tpu.consensus.state  # noqa: F401 — registers families
    from tendermint_tpu.rpc.core import RPCEnv, make_server
    vs, bid, commit, pyv = _small_commit()
    vs.verify_commit("telemetry-chain", bid, 1, commit, verifier=pyv)
    server, _core = make_server(RPCEnv())
    host, port = server.serve("127.0.0.1", 0)
    try:
        conn = http.client.HTTPConnection(host, port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        assert "# TYPE tm_verifier_batch_size histogram" in body
        assert "tm_verifier_batch_size_bucket" in body
        assert "# TYPE tm_consensus_round_duration_seconds histogram" \
            in body
        assert 'tm_verifier_calls_total{backend="python"}' in body
        # every non-comment line is `name{labels} value`
        for line in body.splitlines():
            if not line or line.startswith("#"):
                continue
            name_part, _, value = line.rpartition(" ")
            assert name_part and float(value) is not None
    finally:
        server.stop()


def test_env_off_makes_call_sites_noop():
    """TM_TPU_TELEMETRY=off: instrumented paths record nothing, and a
    config asking for telemetry=True cannot re-enable it."""
    code = (
        "from tendermint_tpu import telemetry\n"
        "assert not telemetry.enabled()\n"
        "telemetry.configure(enabled=True)  # config must NOT win\n"
        "assert not telemetry.enabled()\n"
        "from tendermint_tpu.models.verifier import BatchVerifier\n"
        "from tendermint_tpu.types.keys import PrivKey\n"
        "v = BatchVerifier('python')\n"
        "k = PrivKey.generate(b'\\x01' * 32)\n"
        "assert v.verify_one(k.pubkey.ed25519, b'm', k.sign(b'm'))\n"
        "assert telemetry.value('verifier_batch_size')['count'] == 0\n"
        "assert telemetry.value('verifier_calls_total',\n"
        "                       {'backend': 'python'}) is None\n"
        "print('NOOP-OK')\n"
    )
    env = dict(os.environ, TM_TPU_TELEMETRY="off", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "NOOP-OK" in out.stdout


def test_namespace_configurable():
    telemetry.configure(namespace="acme")
    try:
        assert "acme_verifier_batch_size" in telemetry.expose()
    finally:
        telemetry.configure(namespace="tm")
    with pytest.raises(ValueError):
        telemetry.configure(namespace="Bad Namespace")


def test_consensus_round_metrics_after_committed_heights():
    """Acceptance: after a small in-process consensus run, the round
    duration histogram, step counters and height gauge have samples and
    the trace ring holds the per-step timeline."""
    import tests.test_consensus as tc

    dur0 = telemetry.value("consensus_round_duration_seconds")["count"]
    commits0 = telemetry.value("consensus_commits_total") or 0
    ev0 = len(telemetry.TRACER.events())
    nodes, _ = tc.make_net(1)
    nodes[0].start()
    tc.run_until_height(nodes, 2)
    dur1 = telemetry.value("consensus_round_duration_seconds")["count"]
    assert dur1 >= dur0 + 2                      # one per committed round
    assert telemetry.value("consensus_commits_total") >= commits0 + 2
    assert telemetry.value("consensus_height") >= 2
    assert telemetry.value("consensus_steps_total",
                           {"step": "COMMIT"}) >= 2
    names = {e["name"] for e in telemetry.TRACER.events()[ev0:]}
    assert "cs:finalize_commit" in names
    assert any(n.startswith("cs:") and n != "cs:finalize_commit"
               for n in names)
    assert "tm_consensus_round_duration_seconds_sum" in telemetry.expose()


# ------------------------------------------------------- check_metrics --

def test_check_metrics_lint_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_metrics.py")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
