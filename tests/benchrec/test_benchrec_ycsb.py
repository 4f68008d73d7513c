"""The cell net_4v_ycsb.ycsb_a_zipf: its declaration, its run's clock,
its readers on recorded values, and the whole command's path on the CPU
at toy sizes (300 records of 100 bytes), traced and untraced."""

import json
import os
import time

import pytest

from benchrec_util import REPO, manifest, rehearse

CELL = "net_4v_ycsb.ycsb_a_zipf"
CONTROL = "net_4v_kvstore.writes_steady"
M = manifest()
LAYER = {m["name"]: m for m in M["per_layer"]}
NEW = ["ycsb_read_p50_ms", "ycsb_read_p99_ms", "ycsb_query_p50_ms",
       "ycsb_tree_commit_ms_per_block", "ycsb_dirty_leaves_per_block",
       "ycsb_same_block_rewrite_share", "ycsb_parts_per_block",
       "ycsb_load_s", "ycsb_load_records_per_s",
       "ycsb_state_MB_per_validator"]
# what the load builds moves the set-up it is most of, not the window
OF_THE_LOAD = set(NEW[-3:])
CHECKS = {
    "app_hashes_differing_from_plain_reference",
    "window_reads_whose_proof_does_not_verify",
    "window_reads_differing_from_plain_reference",
    "window_updates_never_committed",
    "acknowledged_updates_missing_from_log",
    "read_backs_unproven_or_differing_from_plain_reference",
    "read_backs_anchored_outside_what_the_audit_certifies",
    "read_backs_served_from_before_the_updates_height",
    "proof_controls_not_rejected",
    "nodes_disagreeing_at_last_height",
    "audit_forged_header_not_rejected_at_its_height"}


def _notes(out: str):
    return [json.loads(x) for x in out.splitlines() if x.startswith("{")]


# ----------------------------------------------------- the declaration

def test_the_cell_its_deployment_and_its_metrics_are_declared():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "net_4v_ycsb"
    conf = next(c for c in M["configs"] if c["name"] == "net_4v_ycsb")
    assert conf["reduced"] in ([], ["recordcount"])
    assert len(conf["source"]) < 200
    with open(os.path.join(REPO, conf["file"])) as f:
        doc = json.load(f)
    with open(os.path.join(REPO, "benchmark/configs/net_4v_kvstore.json")) \
            as f:
        control = json.load(f)
    assert doc["source"] == conf["source"]
    # the control's nodes
    for key in ("validators", "voting_power_each", "rpc_nodes", "app",
                "stores", "transport", "injected_delay_ms",
                "auto_threshold", "consensus"):
        assert doc[key] == control[key], key
    # the source's shapes, uncut
    assert (doc["record_bytes"], doc["read_share"], doc["update_share"],
            doc["zipfian_constant"], doc["commit_backend"]) == (
        1000, 0.5, 0.5, 0.99, "tree")
    assert "prove=true" in doc["reads"]
    assert doc["recordcount"] in (250_000, 500_000, 1_000_000)
    assert (doc["recordcount"] < 1_000_000) == \
        (conf["reduced"] == ["recordcount"]) == \
        ("recordcount" in doc["reduced"])
    # where the load's SHA waves run is the program's constant, with
    # the measurement that set it
    assert "sha_waves" not in doc and "on the host" in doc["chip_layout"]
    assert len(doc["guarantees"]) == 5 and "from_memory" in doc["assumed"]
    assert doc["rehearsal"] == {"recordcount": 300, "record_bytes": 100}
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert CELL in e2e["commit_p50_ms"]["workloads"]
    for name in NEW:
        m = LAYER[name]
        assert m["workloads"] == [CELL] and m["moves"] == (
            "setup_s" if name in OF_THE_LOAD else "commit_p50_ms")
        assert m["layer"] == "state tree and read path"
    # and none of them can only read a constant of the deployment
    assert not {"ycsb_sha_waves_on_device_share",
                "ycsb_proof_bytes_p50"} & set(LAYER)
    # the twelve readers both net cells list gain this cell
    shared = [m["name"] for m in M["per_layer"]
              if {CONTROL, "net_100v.writes_steady", CELL} <=
              set(m.get("workloads", ()))]
    assert len(shared) == 12
    with open(os.path.join(REPO, "benchmark", "traffic",
                           f"{CELL}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           f"{CONTROL}.json")) as f:
        steady = json.load(f)
    assert traffic["driver"] == "fleet_ycsb"
    p = traffic["params"]
    # writes_steady's window, warm-up, drain and audit
    for key in ("conns", "method", "warm_blocks", "drain_s", "drain_max_s",
                "audit_heights", "readback_sample", "trace_seconds",
                "slo_sample"):
        assert p[key] == steady["params"][key], key
    assert p["rate"] % 20 == 0 and f"{p['rate']}/s" in traffic["about"]
    assert p["deadline_s"] == 300 and sum(p["budget_s"].values()) <= 290


def test_the_parent_of_this_cell_fails_at_once_with_keyerror():
    from benchmark.manifest import Manifest
    with pytest.raises(KeyError, match="no workload 'net_4v_ycsb.nope'"):
        Manifest(REPO).cell("net_4v_ycsb.nope")


# ------------------------------------------------------------ the clock

def test_the_clock_takes_this_cells_phases_and_names_the_hung_one(capfd):
    from benchmark.drivers.fleet_procs import OVERDUE_EXIT
    from benchmark.drivers.fleet_ycsb import Clock
    killed, exits = [], []
    clock = Clock(time.time() - 1.0, 1.6, {"records": 5, "load": 75},
                  on_deadline=lambda: killed.append(True),
                  exit_fn=exits.append)
    assert clock.limit["load"] == 150.0 and clock.budget["records"] == 5.0
    with clock.phase("records"):
        time.sleep(0.05)
    with clock.phase("load"):
        assert clock.left() <= 0.6      # never past the deadline
        time.sleep(0.9)                 # the phase that hangs
    assert killed == [True] and exits == [OVERDUE_EXIT]
    err = capfd.readouterr().err
    assert "deadline of 2 s passed in phase 'load'" in err
    doc = json.loads(err[err.index("{"):])
    assert doc["phase"] == "load" and doc["seconds"]["records"] >= 0.05


# ---------------------------------------------------------- the readers

def _reading(client=None, window=(10.0, 20.0)):
    from types import SimpleNamespace
    return SimpleNamespace(client=client or {}, window=window, passes=[],
                           counters={}, trace=None)


def _reader(name):
    from benchmark.manifest import Manifest
    return Manifest(REPO).reader(name)


def test_the_readers_on_recorded_values():
    r = _reading({
        "read_ms": [float(x) for x in range(1, 101)],
        "same_block_rewrite_share": 12.5,
        "blocks": [{"parts": 4, "seen_at": 0.1, "txs": 9, "round": 0},
                   {"parts": 5, "seen_at": 0.4, "txs": 9, "round": 0}],
        "tree_loads": [{"seconds": 2.0, "records": 300, "bytes": 1},
                       {"seconds": 1.0, "records": 300, "bytes": 1}],
        "load_rss_bytes_per_validator": 3.5e8})
    got = {name: _reader(name).read(r) for name in NEW}
    assert got["ycsb_read_p50_ms"] == 50.0
    assert got["ycsb_read_p99_ms"] == 99.0
    assert got["ycsb_same_block_rewrite_share"] == 12.5
    assert got["ycsb_parts_per_block"] == 4.5
    assert got["ycsb_load_s"] == 3.0
    assert got["ycsb_load_records_per_s"] == 200.0
    assert got["ycsb_state_MB_per_validator"] == 350.0


def test_a_run_that_recorded_nothing_reads_nothing():
    r = _reading()
    for name in NEW:
        assert _reader(name).read(r) is None, name


def test_the_harvest_takes_the_windows_spans_out_of_the_ring(monkeypatch):
    from benchmark.ycsb_spans import Harvest
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    monkeypatch.setattr(trace, "TRACER", trace.Tracer(capacity=64))
    try:
        harvest = Harvest(("tree.commit", "app.query"))
        t0 = time.perf_counter()
        harvest.take(t0)
        for version in range(1, 4):
            # more than the ring holds between two takes would be lost;
            # this much is not, though the ring wraps over the run
            for _ in range(15):
                with trace.span("app.query", req=version, prove=1):
                    pass
            a = time.perf_counter()
            trace.complete("tree.commit", a, a + 0.002, req=version,
                           dirty_leaves=10 * version)
            harvest.take(time.perf_counter())
        t1 = time.perf_counter()
        assert trace.TRACER.dropped == 0 and trace.TRACER._written == 48
        for _ in range(40):
            trace.instant("cs:timeout")     # the run goes on; the ring wraps
        assert trace.TRACER.dropped > 0
        r = _reading({"span_rows": harvest.rows()}, window=(t0, t1))
        assert _reader("ycsb_dirty_leaves_per_block").read(r) == 20
        assert _reader("ycsb_tree_commit_ms_per_block").read(r) == \
            pytest.approx(2.0, abs=0.01)
        assert 0 <= _reader("ycsb_query_p50_ms").read(r) < 1.0
        assert len(harvest.rows()["app.query"]) == 45
        # a take that comes after the ring displaced what it wanted
        late = Harvest(("tree.commit",))
        late.take(t0)
        late.take(time.perf_counter())
        assert late.rows() == {"tree.commit": None}
    finally:
        telemetry.set_enabled(was)


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    from benchmark.ycsb_spans import Harvest
    from tendermint_tpu.telemetry import trace
    # the parent commit: its catalogue has none of the three
    for name in ("tree.commit", "tree.load", "app.query"):
        monkeypatch.delitem(trace.SPANS, name)
    harvest = Harvest(("tree.commit", "app.query"))
    harvest.take(1.0)
    harvest.take(2.0)
    r = _reading({"span_rows": harvest.rows()})
    for name in ("ycsb_query_p50_ms", "ycsb_tree_commit_ms_per_block",
                 "ycsb_dirty_leaves_per_block"):
        assert _reader(name).read(r) is None


# ------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def traced():
    import contextlib
    import io
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        line = rehearse(CELL, seed=2**31 + 35, seconds=3.0, trace=True)
    return line, _notes(out.getvalue())


def test_rehearsal_is_correct_and_reports_the_cells_metrics(traced):
    line, notes = traced
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 120         # 40/s for 3 s, reads and updates
    got = set(line["metrics"])
    listed = {m["name"] for m in M["per_layer"]
              if CELL in m.get("workloads", ())}
    assert len(listed) == 12 + len(NEW) and set(NEW) <= listed
    # every per-layer metric that lists the cell finds something to
    # read here, but for the one the profiler's trace of a chip feeds
    assert listed - {"net_device_idle_share"} <= got, listed - got
    v = {k: m["value"] for k, m in line["metrics"].items()}
    assert v["ycsb_read_p50_ms"] > 0 and v["ycsb_query_p50_ms"] > 0
    assert v["ycsb_read_p99_ms"] >= v["ycsb_read_p50_ms"] >= \
        v["ycsb_query_p50_ms"]
    assert v["ycsb_tree_commit_ms_per_block"] > 0
    assert v["ycsb_dirty_leaves_per_block"] >= 0
    assert 0 <= v["ycsb_same_block_rewrite_share"] <= 100
    assert v["ycsb_parts_per_block"] == 1.0             # toy records
    assert v["ycsb_load_s"] > 0 and v["ycsb_load_records_per_s"] > 1000
    assert v["ycsb_state_MB_per_validator"] >= 0
    window = next(n for n in notes if n.get("bench") == "window")
    assert window["reads"] + window["updates"] == 120
    assert 35 <= window["reads"] <= 85                  # half, by the seed
    assert window["answered_reads"] == window["reads"]
    assert window["acked"] == window["updates"]
    steps = next(n for n in notes if n.get("bench") == "steps")
    assert steps["propose_p50_ms"] > 0 and steps["timeouts"] is not None


def test_rehearsal_checks_controls_and_clock(traced):
    _line, notes = traced
    checks = {n["check"]: n for n in notes if n.get("bench") == "check"}
    assert set(checks) == CHECKS
    assert all(n["ok"] and n["limit"] == 0 for n in checks.values())
    backs = next(n for n in notes if n.get("bench") == "read_backs")
    # the controls read above 0: each forged proof refused by both
    assert backs["controls_rejected"]["flipped_sibling"] == \
        backs["controls_rejected"]["wrong_value"] == backs["sampled"] > 0
    audit = next(n for n in notes if n.get("bench") == "lite_audit")
    # anchored at headers the audit certified: before the forged one
    assert backs["audit"][0] <= backs["anchors"][0] <= \
        backs["anchors"][1] < audit["forged_height"] <= backs["audit"][1]
    load = next(n for n in notes if n.get("bench") == "load")
    # every wave of the load on the host, by the program's counter
    assert load["sha_batches"]["device"] == 0 < \
        load["sha_batches"]["native"] + load["sha_batches"]["host"]
    assert load["records"] == 300
    assert [t["records"] for t in load["tree_loads"]] == [300] * 4
    assert audit["outcome"].startswith(f"height {audit['forged_height']}:")
    clock = next(n for n in notes if n.get("bench") == "clock")
    assert clock["phase"] == "stop" and set(clock["seconds"]) == {
        "start", "compile", "records", "load", "warm", "window", "drain",
        "settle", "checks", "stop"}


def test_rehearsal_untraced_line(capfd):
    line = rehearse(CELL, seed=35, seconds=2.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"commit_p50_ms", "setup_s"}
    assert line["attempted"] == 80
    notes = _notes(capfd.readouterr().out)
    assert {n["check"] for n in notes
            if n.get("bench") == "check"} == CHECKS
