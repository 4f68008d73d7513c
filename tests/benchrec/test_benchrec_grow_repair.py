"""`grow_repair_share` (PR 48): the per-layer entry that reads the window
engine's repairs, `sync.repair`, in both join cells: found by name, its
span in the program's catalogue under the layer the entry states, read
in the traced rehearsals beside `grow_judge_share`, which it took its
time from, and nothing read on a program without the span (the parent
commit). test_benchrec_grow.py holds the grow cell's per-layer names as
an exact set, as they were before this entry listed the cell; a PR that
is no `benchmark` PR cannot edit that file, so tests/conftest.py marks
that one test outdated and its assertion is made again here, with the
entry in."""

import pytest

from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

from test_benchrec_grow import CELL, DEVICE_FED, JOIN, LISTED, NEW, SYNC, values

NAME = "grow_repair_share"
SPAN = "sync.repair"


def test_the_entry_is_found_by_name_and_lists_both_join_cells():
    m, = [x for x in manifest()["per_layer"] if x["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "lower",
                 "source": "program_span", "layer": "verifier",
                 "moves": "commits_per_s", "workloads": [CELL, JOIN]}
    reader = Manifest(REPO).reader(NAME)
    assert (reader.LAYER, reader.MOVES) == ("verifier", "commits_per_s")
    # the cells that list it report the end-to-end metric it moves
    rates, = [x for x in manifest()["end_to_end"]
              if x["name"] == "commits_per_s"]
    assert {CELL, JOIN} <= set(rates["workloads"])
    # the constant-set cell does not: its set never gains a key
    assert SYNC not in m["workloads"]


def test_the_grow_cells_per_layer_metrics_are_these_and_no_namesake():
    """test_benchrec_grow.py's assertion of the same name, with this
    entry in."""
    got = {m["name"] for m in Manifest(REPO).metrics(CELL, "per_layer")}
    assert got == LISTED | set(NEW) | {NAME}
    assert not [n for n in got if n.startswith(("join_sync_", "join_apply_",
                                                "join_program_", "join_vc_"))]
    assert NAME in {m["name"]
                    for m in Manifest(REPO).metrics(JOIN, "per_layer")}


def test_the_span_is_the_programs_under_the_layer_the_entry_states():
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain import reactor   # declares the families
    from tendermint_tpu.telemetry.trace import SPANS
    m, = [x for x in manifest()["per_layer"] if x["name"] == NAME]
    assert SPANS[SPAN] == m["layer"] == SPANS["sync.judge"]
    assert telemetry.REGISTRY.get("sync_repairs_total") is reactor._m_repairs
    assert telemetry.REGISTRY.get("sync_repaired_lanes_total") is \
        reactor._m_repaired_lanes


@pytest.mark.parametrize("cell", [CELL, JOIN])
def test_the_traced_rehearsal_reads_it_beside_the_judge(cell):
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(cell, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    got = values(line)
    listed = {m["name"] for m in Manifest(REPO).metrics(cell, "per_layer")}
    assert NAME in listed and NAME in got
    # no device here: what a trace or a device counter feeds is left out
    assert listed - DEVICE_FED <= set(got)
    assert 0 < got[NAME] < 100 and 0 < got["grow_judge_share"] < 100
    # the same lanes as before, verified once more each
    assert 0 < got["join_lanes_discarded_share"] < 40
    assert 1 < got["join_sigs_per_needed"] < 1.4
    assert got["join_reverified_share"] == 0
    # the repairs left the judge nothing to verify again, but the vote
    # that claims another member's address in the grow cell's control
    events = telemetry.TRACER.events()
    judged = [e["args"]["again"] for e in events if e["name"] == "sync.judge"]
    assert len(judged) > 96 and sum(judged) <= (2 if cell == CELL else 0)
    repairs = [e for e in events if e["name"] == SPAN]
    assert repairs and sum(e["args"]["lanes"] for e in repairs) > 0


def test_an_untraced_run_leaves_it_out():
    line = rehearse(CELL)
    assert set(line["metrics"]) == {"commits_per_s", "setup_s"}


def test_a_program_without_the_span_leaves_it_out(monkeypatch):
    """The parent commit: no `sync.repair` in its catalogue. The reader
    returns nothing and does not raise; `grow_judge_share` reads on."""
    from types import SimpleNamespace
    from tendermint_tpu.telemetry import trace
    man = Manifest(REPO)
    r = SimpleNamespace(
        window=(0.0, 1.0), passes=[SimpleNamespace(start=0.0, seconds=1.0)],
        counters={})
    assert man.reader(NAME).read(r) is not None
    assert man.reader("grow_judge_share").read(r) is not None
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items() if k != SPAN})
    assert man.reader(NAME).read(r) is None
    assert man.reader("grow_judge_share").read(r) is not None
    # and with no passes at all
    r.passes = []
    assert man.reader(NAME).read(r) is None
