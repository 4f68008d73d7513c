"""BENCHMARK.json is well formed and every file it names is there."""

import importlib
import json
import os
import re

import pytest

from benchrec_util import REPO, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest()
CELLS = {w["name"]: w for w in M["workloads"]}
E2E = {m["name"]: m for m in M["end_to_end"]}
LAYER = {m["name"]: m for m in M["per_layer"]}


def reported_by(metric: dict):
    return set(metric.get("workloads") or CELLS)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark", "tests/benchrec"]
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(M["command"]) <= 32
    assert len(json.dumps(M)) < 64 * 1024
    # a full check fits the driver's day with all 24 cells
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_at_most_four_end_to_end_metrics_besides_setup():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    assert len(E2E) - 1 <= 4


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell(cell):
    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == 1, "no cell takes four chips"
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert w["config"] in {c["name"] for c in M["configs"]}
    traffic = os.path.join(REPO, "benchmark", "traffic", f"{cell}.json")
    with open(traffic) as f:
        doc = json.load(f)
    importlib.import_module(f"benchmark.drivers.{doc['driver']}").run
    # setup_s, one other end-to-end metric, one per-layer metric
    e2e = [n for n, m in E2E.items() if cell in reported_by(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in reported_by(m) for m in LAYER.values())


@pytest.mark.parametrize("config", [c["name"] for c in M["configs"]])
def test_config(config):
    c = next(c for c in M["configs"] if c["name"] == config)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("benchmark/configs/")
    assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    with open(os.path.join(REPO, c["file"])) as f:
        doc = json.load(f)
    assert any(w["config"] == config for w in M["workloads"])
    for key in c["reduced"]:
        assert NAME.match(key) and key in doc and key in doc["reduced"]
        assert not key.endswith(("_dim", "_rank", "_bytes"))
    assert doc["guarantees"], "a deployment states its guarantees"


def test_net_timeouts_are_upstreams_defaults_written_out():
    from tendermint_tpu.config import ConsensusConfig
    with open(os.path.join(REPO, "benchmark/configs/net_4v_kvstore.json")) as f:
        written = json.load(f)["consensus"]
    default = ConsensusConfig()
    assert written == {k: getattr(default, k) for k in written}
    assert written["timeout_commit"] == 1000
    assert written["skip_timeout_commit"] is False
    assert len(written) == 8


@pytest.mark.parametrize("metric", sorted(E2E))
def test_end_to_end_metric(metric):
    m = E2E[metric]
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert reported_by(m) <= set(CELLS)
    reader = importlib.import_module(f"benchmark.metrics.{metric}")
    assert reader.MOVES is None and callable(reader.read)


@pytest.mark.parametrize("metric", sorted(LAYER))
def test_per_layer_metric(metric):
    m = LAYER[metric]
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert metric not in E2E
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert not metric.endswith("_roofline"), "no roofline without a peak"
    # the metric it should move is reported by each of its cells
    moved = E2E[m["moves"]]
    assert reported_by(m) <= reported_by(moved)
    reader = importlib.import_module(f"benchmark.metrics.{metric}")
    assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]


def test_writes_steady_tail_is_p95_and_its_rate_is_per_layer():
    steady = "net_4v_kvstore.writes_steady"
    # the tail is p95, and a per-layer metric: PERF.md section 2 says why
    assert steady in reported_by(LAYER["commit_p95_ms"])
    assert "commit_p99_ms" not in E2E and "commit_p95_ms" not in E2E
    assert steady in reported_by(E2E["commit_p50_ms"])
    assert "committed_tx_per_s" not in E2E     # judged above the knee or not at all
    assert steady in reported_by(LAYER["steady_committed_tx_per_s"])


def test_every_file_under_paths_is_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(d, name), REPO)
                assert ok.match(rel), rel


def test_peaks_table_has_sources_and_refuses_unknown_devices():
    from benchmark import device
    assert device.peaks("TPU v5 lite")["source"]
    with pytest.raises(device.NoChip):
        device.peaks("TPU v9 imaginary")
