"""The four per-layer metrics PR 27 appended to BENCHMARK.json
(`lite_collect_headers_share`, `lite_collect_votes_share`,
`lite_columns_share`, `columns_share`): their entries, by name; what
their readers do on a program that has no such span or family (the
parent commit) and on one that sent nothing to a device (a rehearsal);
and the traced rehearsals of the cells that list them."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchrec"))

from benchmark import program_spans                        # noqa: E402
from benchmark.manifest import Manifest                    # noqa: E402
from benchmark.metrics import (columns_share,              # noqa: E402
                               lite_collect_headers_share,
                               lite_collect_votes_share, lite_columns_share)
from benchrec_util import REPO, manifest, rehearse         # noqa: E402

LITE = "chain_64v.lite_certify"
SYNC = "chain_64v.fastsync_5ktx"
COMMIT = "commit_10kv.verify_commit"
FAMILY = "verifier_batch_sigs_total"

ENTRIES = {
    "lite_collect_headers_share": (
        lite_collect_headers_share, "lower", "program_span",
        "headers_per_s", [LITE]),
    "lite_collect_votes_share": (
        lite_collect_votes_share, "lower", "program_span",
        "headers_per_s", [LITE]),
    "lite_columns_share": (
        lite_columns_share, "higher", "program_counter",
        "headers_per_s", [LITE]),
    "columns_share": (
        columns_share, "higher", "program_counter",
        "commits_per_s", [SYNC, COMMIT]),
}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_and_its_reader(name):
    reader, better, source, moves, cells = ENTRIES[name]
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    assert m == {"name": name, "unit": "%", "better": better,
                 "source": source, "layer": "verifier", "moves": moves,
                 "workloads": cells}
    man = Manifest(REPO)
    assert man.reader(name) is reader
    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    for cell in cells:
        assert name in [x["name"] for x in man.metrics(cell, "per_layer")]
        assert moves in [x["name"] for x in man.metrics(cell, "end_to_end")]


def test_the_single_commit_cell_keeps_its_metrics_and_gains_one():
    """What tests/benchrec/test_benchrec_verify_commit.py::
    test_the_cell_and_its_metrics_are_declared asserts (marked in
    tests/conftest.py since `columns_share` lists the cell), by name
    and open to later entries."""
    span_fed = {"vc_collect_share", "vc_wait_share", "vc_check_share",
                "vc_prep_share", "vc_predecomp_share", "vc_enqueue_share",
                "vc_starved_share"}
    counted = {"vc_commit_p50_ms", "vc_predecomp_reuse_share",
               "vc_h2d_bytes_per_sig"}
    shared = {"sigs_on_device_share", "pad_waste_share",
              "kernel_busy_share", "kernel_sigs_per_s",
              "device_idle_share", "device_peak_mem_MB",
              "compiles_in_window", "setup_compile_s"}
    doc = manifest()
    cell, = [w for w in doc["workloads"] if w["name"] == COMMIT]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "commit_10kv", "verify_commit", 1)
    config, = [c for c in doc["configs"] if c["name"] == "commit_10kv"]
    assert config["reduced"] == []
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert {SYNC, COMMIT} <= set(e2e["commits_per_s"]["workloads"])
    assert e2e["commits_per_s"]["bound"] == 0.14
    by = {m["name"]: m for m in doc["per_layer"]}
    man = Manifest(REPO)
    listed = {m["name"] for m in man.metrics(COMMIT, "per_layer")}
    assert span_fed | counted | shared | {"columns_share"} <= listed
    assert all(by[name]["moves"] == "commits_per_s" for name in listed)
    assert {m["name"] for m in man.metrics(COMMIT, "end_to_end")} == {
        "commits_per_s", "setup_s"}
    for name in span_fed | counted:
        m, reader = by[name], man.reader(name)
        assert m["workloads"] == [COMMIT] and m["moves"] == "commits_per_s"
        assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    for name in shared:                 # one name for one reading
        assert {SYNC, COMMIT} <= set(by[name]["workloads"])
        assert by[name]["moves"] == man.reader(name).MOVES == \
            "commits_per_s"
        assert "vc_" + name not in by


@pytest.fixture
def family():
    """The program's own family, telemetry on, counting from zero."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.models import verifier     # declares the family
    fam = telemetry.REGISTRY.get(FAMILY)
    assert fam is verifier._m_batch_sigs
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    held = {form: fam.labels(form).value for form in ("columns", "items")}
    for form in held:
        fam.labels(form).value = 0.0
    yield fam
    for form, value in held.items():
        fam.labels(form).value = value
    telemetry.set_enabled(was)


@pytest.mark.parametrize("reader", [lite_columns_share, columns_share])
def test_the_share_of_columns_is_columns_over_both_forms(
        monkeypatch, family, reader):
    from tendermint_tpu import telemetry
    from tendermint_tpu.models import verifier
    fake = verifier.BatchVerifier("python")
    monkeypatch.setattr(verifier, "_default", fake)
    family.labels("columns").inc(32_768 * 3)
    assert reader.read(None) is None    # nothing of it went to a device
    fake.stats["jax_sigs"] = 32_768 * 3
    assert reader.read(None) == pytest.approx(100.0)
    family.labels("items").inc(32_768)
    assert reader.read(None) == pytest.approx(75.0)
    # the parent commit: the registry has no such family
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    assert program_spans.counter_total(FAMILY) is None
    assert reader.read(None) is None


@pytest.mark.parametrize("reader, span", [
    (lite_collect_headers_share, "lite.headers"),
    (lite_collect_votes_share, "lite.votes")])
def test_a_program_without_the_span_reads_nothing(monkeypatch, reader,
                                                  span):
    from types import SimpleNamespace
    from tendermint_tpu.telemetry import trace
    r = SimpleNamespace(window=(0.0, 1.0), passes=[
        SimpleNamespace(start=0.0, seconds=1.0)])
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items() if k != span})
    assert reader.read(r) is None


def test_the_lite_rehearsal_splits_collect_and_leaves_the_counter_out(
        family):
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    got = values(line)
    headers, votes = (got["lite_collect_headers_share"],
                      got["lite_collect_votes_share"])
    assert headers > 0 and votes > 0
    # the two passes nest in lite.collect and fill it: no seventh leg
    assert headers + votes <= got["lite_collect_share"] + 1e-9
    assert headers + votes >= 0.9 * got["lite_collect_share"]
    # host-verified batches: nothing was dispatched to a device
    assert "lite_columns_share" not in got
    assert "lite_h2d_bytes_per_sig" not in got


@pytest.mark.parametrize("cell", [SYNC, COMMIT])
def test_a_rehearsal_on_the_host_leaves_columns_share_out(family, cell):
    line = rehearse(cell, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert "columns_share" not in line["metrics"]


def test_the_lite_line_of_a_program_without_the_split(monkeypatch, family):
    """The parent commit with these files laid over it: a line, with
    the three lite metrics left out and the rest as they were."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items()
        if k not in ("lite.headers", "lite.votes")})
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True
    got = values(line)
    assert not {"lite_collect_headers_share", "lite_collect_votes_share",
                "lite_columns_share"} & set(got)
    assert got["lite_collect_share"] > 0 and got["lite_check_share"] > 0
