"""Chaos plane tests: seeded fault schedule determinism, the tier-1
smoke scenario (drop + delay + one crash-restart, seconds on the CI
host), the zero-overhead off-hatch, monitor self-checks (an oracle that
cannot fail proves nothing), and the full acceptance scenario (slow)."""

import shutil
import tempfile
import types

import pytest

from tendermint_tpu.chaos.monitor import InvariantMonitor
from tendermint_tpu.chaos.schedule import FaultSchedule


# --------------------------------------------------------- schedule --

def _drive(schedule, n=300):
    """Synthetic deterministic event stream through every decision."""
    for step in range(n):
        schedule.link_deliveries(step, step % 4, (step + 1) % 4, "vote")


def test_same_seed_identical_fault_sequence():
    spec = {"drop": 0.1, "delay": 0.2, "duplicate": 0.05,
            "reorder": 0.05}
    a, b = FaultSchedule(spec, seed=11), FaultSchedule(spec, seed=11)
    _drive(a)
    _drive(b)
    assert a.signature() == b.signature()
    assert a.counts == b.counts and a.counts  # faults actually fired

    c = FaultSchedule(spec, seed=12)
    _drive(c)
    assert a.signature() != c.signature()


def test_schedule_rejects_unknown_crash_point():
    with pytest.raises(ValueError, match="unknown crash point"):
        FaultSchedule({"crashes": [{"node": 0, "point": "no_such"}]})


def test_pinned_spec_signatures():
    """Back-compat pin (ISSUE 11 satellite): the geo/churn spec keys
    must not shift a single RNG draw for any PRE-EXISTING spec — the
    fault sequence of every committed scenario is part of the
    replayability contract. These digests were recorded on the
    pre-geo/churn code; if this test fails, a code change silently
    rewrote every pinned seeded trajectory."""
    import hashlib
    from tendermint_tpu.chaos.runner import ACCEPTANCE_SPEC, SMOKE_SPEC

    def drive_digest(spec, seed=11, n=400, nodes=4):
        s = FaultSchedule(spec, seed=seed)
        for step in range(n):
            for src in range(nodes):
                for dst in range(nodes):
                    if src != dst:
                        s.link_deliveries(step, src, dst, "vote")
        return hashlib.sha256(repr(s.signature()).encode()).hexdigest()

    rate_spec = {"drop": 0.1, "delay": 0.2, "duplicate": 0.05,
                 "reorder": 0.05}
    assert drive_digest(ACCEPTANCE_SPEC) == (
        "e6ac7aee7d9e7877f8ec0d8003457ab3462c1000d0f707aec1c0b910148f6331")
    assert drive_digest(SMOKE_SPEC) == (
        "d2feacb993a35596ec39f6840ad1419d925165d7b8c307d8bb3d0bdbbadaad0c")
    assert drive_digest(rate_spec) == (
        "d3c4ea864a6572f7792871ed4639eb0e15792ccebb061cf3b494d52cd3fa70d6")


def test_geo_profile_shapes_links_deterministically():
    """Geo matrices: cross-region messages pick up the profile's
    latency (+ seeded jitter), intra-region ones don't; losses and
    throttles are seeded (same seed = same sequence) and recorded as
    geo_* fault kinds; regions assign round-robin unless mapped."""
    spec = {"geo": {"profile": "wan3"}, "drop": 0.02}

    def drive(seed):
        s = FaultSchedule(spec, seed=seed)
        for step in range(300):
            for src in range(6):
                for dst in range(6):
                    if src != dst:
                        s.link_deliveries(step, src, dst, "vote")
        return s

    a, b = drive(5), drive(5)
    assert a.signature() == b.signature()
    assert a.counts.get("geo_drop", 0) > 0
    assert drive(6).signature() != a.signature()

    s = FaultSchedule({"geo": {"profile": "wan3"}})
    assert [s.region_of(i) for i in range(6)] == [0, 1, 2, 0, 1, 2]
    # region 0 -> 2 carries wan3's 5-step base latency (+ jitter);
    # 0 -> 3 is intra-region region-0 traffic: free
    assert min(s.link_deliveries(1, 0, 2, "vote")) >= 5
    assert s.link_deliveries(1, 0, 3, "vote") == [0]
    # explicit assignment overrides round-robin
    s2 = FaultSchedule({"geo": {"profile": "wan2",
                                "assign": {0: 1, 1: 1, 2: 0}}})
    assert s2.region_of(0) == 1 and s2.region_of(2) == 0
    assert s2.region_of(5) == 1  # unmapped: round-robin over 2 regions


def test_geo_bandwidth_cap_spills_to_later_steps():
    """A thin long-haul pipe queues, it does not destroy: messages
    beyond the per-step cap on one region pair are DELAYED by their
    queue position and recorded as geo_throttle."""
    spec = {"geo": {"latency_steps": [[0, 1], [1, 0]],
                    "jitter_steps": 0,
                    "bandwidth_msgs": [[0, 3], [3, 0]]}}
    s = FaultSchedule(spec, seed=1)
    delays = [s.link_deliveries(7, 0, 1, "vote")[0] for _ in range(7)]
    # first 3 ride the base latency; 4-6 spill 1 step; 7th spills 2
    assert delays == [1, 1, 1, 2, 2, 2, 3]
    assert s.counts.get("geo_throttle") == 4
    # a new step resets the pipe
    assert s.link_deliveries(8, 0, 1, "vote") == [1]


def test_geo_and_churn_spec_validation():
    with pytest.raises(ValueError, match="unknown geo profile"):
        FaultSchedule({"geo": {"profile": "atlantis"}})
    with pytest.raises(ValueError, match="unknown geo spec key"):
        FaultSchedule({"geo": {"profile": "wan3", "latencey": 1}})
    with pytest.raises(ValueError, match="must be 2x2"):
        FaultSchedule({"geo": {"latency_steps": [[0, 1], [1]]}})
    with pytest.raises(ValueError, match="unknown churn op"):
        FaultSchedule({"churn": {"ops": ["jion"]}})
    with pytest.raises(ValueError, match="unknown churn spec key"):
        FaultSchedule({"churn": {"every": 3}})
    c = FaultSchedule({"churn": {"standby": 2}}).churn
    assert c["ops"] == ["join", "leave", "stake"]
    assert c["every_heights"] == 2 and c["standby"] == 2


def test_partition_and_skew_lookup():
    s = FaultSchedule({"partitions": [{"start": 10, "stop": 20,
                                       "groups": [[0], [1, 2]]}],
                       "clock_skew": {"2": 3}})
    assert s.cross_partition(15, 0, 1)
    assert not s.cross_partition(15, 1, 2)
    assert not s.cross_partition(25, 0, 1)  # healed
    assert s.clock_skew == {2: 3}


# ------------------------------------------------------------ knobs --

def test_chaos_off_is_zero_overhead_noop(monkeypatch):
    from tendermint_tpu import chaos
    monkeypatch.delenv("TM_TPU_CHAOS", raising=False)
    chaos.configure("off", 0)
    link = object()
    assert chaos.maybe_wrap_link(link, "peer") is link  # same object

    monkeypatch.setenv("TM_TPU_CHAOS", "drop=0.5,seed=3")
    wrapped = chaos.maybe_wrap_link(link, "peer")
    assert wrapped is not link
    from tendermint_tpu.p2p.fuzz import FuzzedLink
    assert isinstance(wrapped, FuzzedLink)

    # env wins over configure(); off in env beats a configured spec
    chaos.configure("drop=0.5", 1)
    monkeypatch.setenv("TM_TPU_CHAOS", "off")
    assert chaos.maybe_wrap_link(link, "peer") is link


def test_spec_string_parse_rejects_typos():
    from tendermint_tpu import chaos
    assert chaos.parse_spec("drop=0.1,delay=0.2,delay_ms=25,seed=9") == {
        "drop": 0.1, "delay": 0.2, "delay_ms": 25.0, "seed": 9}
    with pytest.raises(ValueError, match="unknown chaos spec key"):
        chaos.parse_spec("dorp=0.1")


# ---------------------------------------------------------- monitor --

def _fake_block(height, tag=b"A", evidence=()):
    blk = types.SimpleNamespace()
    blk.header = types.SimpleNamespace(height=height)
    blk.evidence = types.SimpleNamespace(evidence=list(evidence))
    blk.hash = lambda: tag * 32
    return blk


def test_monitor_detects_disagreement():
    m = InvariantMonitor()
    m._on_commit(1, 0, _fake_block(3, b"A"))
    m._on_commit(2, 1, _fake_block(3, b"B"))  # different block, same h
    assert [v["invariant"] for v in m.violations] == ["agreement"]


def test_monitor_detects_height_regression():
    m = InvariantMonitor()
    m._on_commit(1, 0, _fake_block(3, b"A"))
    m._on_commit(2, 0, _fake_block(3, b"A"))  # same node re-commits 3
    assert [v["invariant"] for v in m.violations] == ["validity"]


def test_monitor_flags_missing_evidence_and_liveness():
    m = InvariantMonitor()
    m.expect_double_sign(("ab", 2, 0, 1))
    m._on_commit(5, 0, _fake_block(2))
    sched = FaultSchedule({"partitions": [
        {"start": 1, "stop": 10, "groups": [[0], [1]]}]})
    rep = m.finalize(sched, final_step=400, liveness_bound=50)
    kinds = sorted(v["invariant"] for v in rep["violations"])
    # the double-sign never committed AND no commit followed the heal
    assert kinds == ["evidence", "liveness"]


# ------------------------------------------------------------ runs --

def test_chaos_smoke_drop_delay_crash():
    """Tier-1 seeded smoke (ISSUE 4 satellite): drop + delay + one
    crash-restart through WAL/handshake replay, zero invariant
    violations, all nodes caught up. Seconds on the 1-core host."""
    from tendermint_tpu.chaos.runner import SMOKE_SPEC, run_chaos
    r = run_chaos(spec=SMOKE_SPEC, seed=7, target_height=4,
                  max_steps=400)
    assert r["violations"] == []
    assert r["max_height"] >= 4
    assert set(r["heights"]) == {0, 1, 2, 3}
    assert min(r["heights"].values()) >= 4
    f = r["faults_injected"]
    assert f.get("drop", 0) > 0 and f.get("delay", 0) > 0
    assert f.get("crash") == 1 and f.get("restart") == 1
    assert r["checks"]["agreement"] > 0


def test_relay_gossip_dedup_skips_redundant_deliveries():
    """ISSUE 12 satellite: a duplicate-heavy run must skip re-
    delivering byte-identical vote/part messages a destination already
    consumed (the O(n²) residual PR 11 flagged) — with the SAME
    verdict: zero violations, every node caught up. And dedup must not
    break the determinism witness: two runs of one (spec, seed) still
    produce one fault log."""
    from tendermint_tpu.chaos.runner import run_chaos
    spec = {"drop": 0.02, "duplicate": 0.5, "delay": 0.05,
            "delay_steps": [1, 2]}
    r1 = run_chaos(spec=spec, seed=11, target_height=4, max_steps=400)
    assert r1["violations"] == []
    assert r1["max_height"] >= 4
    assert r1["relay_dedup_skips"] > 0, \
        "duplicate faults must produce provably-redundant deliveries"
    r2 = run_chaos(spec=spec, seed=11, target_height=4, max_steps=400)
    assert r1["fault_log_sha256"] == r2["fault_log_sha256"]
    assert r1["relay_dedup_skips"] == r2["relay_dedup_skips"]


@pytest.mark.slow
def test_chaos_acceptance_scenario():
    """ACCEPTANCE_SPEC, the full scenario: drop/delay/duplicate/reorder,
    partition + heal, crash-restart, equivocating validator, clock
    skew — zero violations, every injected double-sign committed."""
    from tendermint_tpu.chaos.runner import run_chaos
    r = run_chaos(seed=42)
    assert r["violations"] == []
    f = r["faults_injected"]
    for kind in ("drop", "delay", "duplicate", "reorder", "partition",
                 "heal", "crash", "restart", "equivocation"):
        assert f.get(kind, 0) >= 1, f"{kind} never fired: {f}"
    ev = r["evidence"]
    assert ev["injected_double_signs"] > 0
    assert ev["committed"] == ev["injected_double_signs"]
    assert r["recovery"]["latency_steps"]["n"] >= 3


@pytest.mark.slow
def test_chaos_partition_heals_and_recovers():
    """Partition-only schedule: the majority side keeps committing, the
    isolated node catches up after the heal (buffered delivery + the
    runner's reactor-style catch-up), liveness check passes."""
    from tendermint_tpu.chaos.runner import run_chaos
    spec = {"partitions": [{"start": 20, "stop": 60,
                            "groups": [[0], [1, 2, 3]]}]}
    r = run_chaos(spec=spec, seed=5, target_height=8, max_steps=600)
    assert r["violations"] == []
    assert min(r["heights"].values()) >= 8
    assert r["faults_injected"].get("partition") == 1
    assert r["faults_injected"].get("heal") == 1


def test_switch_links_get_chaos_wrapped_and_still_deliver(monkeypatch):
    """TM_TPU_CHAOS on a real switch: both peers' links come back as
    FuzzedLinks (per-frame fault injection live on the encrypted burst
    path) and traffic still flows through a delay-only spec."""
    from tests.test_p2p import (EchoReactor, connect_switches,
                                make_switch, wait_for)
    from tendermint_tpu.p2p.fuzz import FuzzedLink

    monkeypatch.setenv("TM_TPU_CHAOS", "delay=0.3,delay_ms=5,seed=1")
    r1 = EchoReactor("echo", 0x10, echo=False)
    r2 = EchoReactor("echo", 0x10, echo=True)
    sw1 = make_switch(seed=b"\x01" * 32, encrypt=True)
    sw2 = make_switch(seed=b"\x02" * 32, encrypt=True)
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    sw1.start()
    sw2.start()
    p1, p2 = connect_switches(sw1, sw2)
    try:
        assert isinstance(p1.mconn.link, FuzzedLink)
        assert isinstance(p2.mconn.link, FuzzedLink)
        assert p1.send(0x10, b"through-chaos")
        assert wait_for(lambda: r2.received, timeout=5.0)
        assert r2.received[0][1] == b"through-chaos"
    finally:
        sw1.stop()
        sw2.stop()


def test_violation_trace_is_written_and_replayable(tmp_path):
    """A run asked for a trace dumps seed + spec + fault log + commits;
    the trace's (spec, seed) rebuild an identical schedule."""
    import json
    from tendermint_tpu.chaos.runner import run_chaos
    spec = {"drop": 0.05, "delay": 0.1}
    trace = str(tmp_path / "trace.json")
    r = run_chaos(spec=spec, seed=3, target_height=3, max_steps=300,
                  trace_path=trace)
    assert r["violations"] == []
    doc = json.load(open(trace))
    assert doc["seed"] == 3 and doc["spec"] == spec
    assert doc["fault_log"]  # replayed decisions are all there
    assert doc["commits"]
