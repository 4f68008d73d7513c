"""The reduction from a trace to busy, idle and per-operation seconds,
on a small recorded event list (one device plane with a modules line
and an ops line, and the harness's spans on the same clock)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


@pytest.fixture()
def events():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        doc = json.load(f)
    return {"devices": {p: {ln: [tuple(e) for e in evs]
                            for ln, evs in lines.items()}
                        for p, lines in doc["devices"].items()},
            "host_spans": [tuple(e) for e in doc["host_spans"]]}


def test_busy_is_the_union_and_idle_the_rest(events):
    r = tr.reduce_events(events)
    # window 0..100 ms; ops busy 10-30, 40-45, 60-80 (module spans the
    # same intervals and must not count twice) = 45 ms
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["devices"] == 1


def test_operations_by_name_from_the_ops_line(events):
    r = tr.reduce_events(events)
    ops = dict(r["device_ops"])
    assert ops["_verify_pre_pallas.1"] == pytest.approx(0.040)
    assert ops["fusion.1"] == pytest.approx(0.005)
    assert "jit_module" not in ops
    assert tr.kernel_seconds(r) == pytest.approx(0.040)
    assert r["device_ops"][0][0] == "_verify_pre_pallas.1"


def test_gaps_are_named_after_the_innermost_span_open(events):
    r = tr.reduce_events(events)
    gaps = r["idle_gaps"]
    # 80-100 (20 ms) inside "decode"; 45-60 (15) inside "collect",
    # which lies inside "sync_pass"; 0-10 and 30-40 (10 each)
    assert gaps[0] == ["decode", pytest.approx(0.020)]
    assert gaps[1] == ["collect", pytest.approx(0.015)]
    assert sorted(g[0] for g in gaps[2:]) == ["sync_pass", "unattributed"]
    assert sum(g[1] for g in gaps) == pytest.approx(0.055)


def test_events_outside_the_window_are_clipped(events):
    events["devices"]["/device:TPU:0"]["XLA Ops"].append(
        ("late.1", 95 * MS, 20 * MS))
    r = tr.reduce_events(events)
    assert r["busy_s"] == pytest.approx(0.050)
    assert dict(r["device_ops"])["late.1"] == pytest.approx(0.005)


def test_no_device_plane_gives_nothing(events):
    assert tr.reduce_events({"devices": {}, "host_spans": []}) is None
    assert tr.merge([None]) is None and tr.kernel_seconds(None) == 0.0


def test_a_traced_part_with_an_idle_device_still_counts(events):
    """A net cell's traced seconds of window hold no device operation,
    so the trace has no device plane: window time with nothing busy,
    one gap named after the span that was open."""
    idle = tr.reduce_events({"devices": {}, "host_spans": [
        ("profile", 0, 8000 * MS), ("fleet_window", 0, 8000 * MS)]})
    assert idle["busy_s"] == 0 and idle["window_s"] == pytest.approx(8.0)
    assert idle["idle_gaps"] == [["fleet_window", pytest.approx(8.0)]]
    both = tr.merge([idle, tr.reduce_events(events)])
    assert both["window_s"] == pytest.approx(8.1)
    assert both["idle_share"] == pytest.approx(1 - 0.045 / 8.1)
    assert both["devices"] == 1


def test_two_profiled_parts_add_up(events):
    one = tr.reduce_events(events)
    both = tr.merge([one, one])
    assert both["busy_s"] == pytest.approx(0.090)
    assert both["window_s"] == pytest.approx(0.200)
    assert both["idle_share"] == pytest.approx(0.55)
    assert dict(both["device_ops"])["fusion.1"] == pytest.approx(0.010)


def test_busy_is_averaged_over_device_planes(events):
    events["devices"]["/device:TPU:1"] = {"XLA Ops": [("x.1", 0, 10 * MS)]}
    r = tr.reduce_events(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.045 + 0.010) / 2)


def test_union_merges_and_sorts():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 7)]) == [(1, 4), (5, 7)]
