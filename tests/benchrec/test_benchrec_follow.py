"""The cell `chain_100v_churn.lite_follow`: its entries in the manifest,
found by name; its configuration as the source has it, with what was
cut and what was assumed; its rehearsal on the CPU (8 validators, 48
headers, 16 changes of set, windows of 8, host-verified), correct and
reporting its metrics; the controls that have to come out as not
correct; and a program that lacks what the cell reads."""

import json

import pytest

from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

CELL = "chain_100v_churn.lite_follow"
CONFIG = "chain_100v_churn"
LITE = "chain_64v.lite_certify"

NEW = {"follow_transition_share", "follow_sethash_share"}
BY_LEG = {"follow_collect_share", "follow_prep_share",
          "follow_predecomp_share", "follow_enqueue_share",
          "follow_wait_share", "follow_check_share", "follow_starved_share",
          "follow_predecomp_reuse_share"}
SHARED = {"lite_kernel_busy_share", "lite_kernel_sigs_per_s",
          "lite_device_idle_share", "lite_device_peak_mem_MB",
          "lite_compiles_in_window", "lite_setup_compile_s",
          "lite_pad_waste_share", "lite_sigs_on_device_share",
          "lite_verify_wall_share"}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


# ------------------------------------------------------------ the manifest

def test_the_cell_and_its_configuration_are_declared():
    doc = manifest()
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="lite_follow", chips=1)
    assert len(cell["why"]) <= 200 and "3.8" in cell["why"]
    cfg, = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert cfg["file"] == "benchmark/configs/chain_100v_churn.json"
    assert cfg["reduced"] == ["lite_headers"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for part in ("dynamic_certifier.go", "inquiring_certifier.go:137-163",
                 "performance_test.go", "configs[4]", "Cosmos Hub"):
        assert part in cfg["source"]
    # a light client's cell: headers certified a second, under the
    # bound the constant-set cell has
    rates = {m["name"]: m for m in doc["end_to_end"]}
    assert CELL in rates["headers_per_s"]["workloads"]
    assert rates["headers_per_s"]["bound"] == 0.05
    assert CELL not in rates["commits_per_s"]["workloads"]
    man = Manifest(REPO)
    assert {m["name"] for m in man.metrics(CELL, "end_to_end")} == {
        "headers_per_s", "setup_s"}
    assert man.traffic(cell)["driver"] == "follow"
    assert man.driver("follow").run


@pytest.mark.parametrize("name", sorted(NEW | BY_LEG))
def test_a_metric_of_its_own_lists_this_cell_alone(name):
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    assert m["workloads"] == [CELL] and m["moves"] == "headers_per_s"
    assert m["unit"] == "%"
    reader = Manifest(REPO).reader(name)
    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    if name in BY_LEG:      # the constant-set cell's reader, and entry
        twin = name.replace("follow_", "lite_")
        assert reader.read is Manifest(REPO).reader(twin).read
        t, = [x for x in manifest()["per_layer"] if x["name"] == twin]
        assert t["workloads"] == [LITE]
        assert {k: m[k] for k in ("better", "source", "layer")} == {
            k: t[k] for k in ("better", "source", "layer")}
    else:
        assert m["source"] == "program_span" and m["better"] == "lower"


@pytest.mark.parametrize("name", sorted(SHARED))
def test_a_shared_reader_lists_this_cell_too(name):
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    assert m["workloads"][:1] == [LITE] and CELL in m["workloads"]
    assert m["moves"] == "headers_per_s"
    assert Manifest(REPO).reader(name).MOVES == "headers_per_s"


def test_the_cells_per_layer_metrics_are_these():
    got = {m["name"] for m in Manifest(REPO).metrics(CELL, "per_layer")}
    assert got == NEW | BY_LEG | SHARED


def test_every_span_and_counter_a_new_reader_names_is_the_programs():
    import os
    import re
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite import certifier      # declares the families
    from tendermint_tpu.telemetry.trace import SPANS
    spans, families = set(), set()
    for name in NEW:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               name + ".py")) as f:
            body = f.read().split('"""', 2)[2]
        spans |= set(re.findall(r'"(lite\.[a-z]+)"', body))
        families |= set(re.findall(r'"(lite_[a-z_]+_total)"', body))
    assert spans == {"lite.transition", "lite.sethash"} <= set(SPANS)
    assert families == {"lite_transitions_total", "lite_windows_total"}
    assert families <= set(telemetry.REGISTRY.names())
    assert telemetry.REGISTRY.get("lite_windows_total") is certifier._m_windows


def test_the_configuration_is_the_sources_with_its_cuts_named():
    from tendermint_tpu.lite.certifier import default_window
    cfg = Manifest(REPO).config(CONFIG)
    with open(f"{REPO}/benchmark/configs/net_100v.json") as f:
        net = json.load(f)
    assert cfg["validators"] == cfg["validator_cap"] == 100
    assert cfg["signers_per_commit"] == 100
    assert cfg["stake_scale"] == net["stake_scale"]
    assert "1000000 // (r + 2)" in cfg["stake"]
    assert set(cfg["reduced"]) == {"lite_headers"}
    assert cfg["lite_headers"] == 4096
    assert (cfg["stake_changes"], cfg["membership_changes"]) == (1024, 64)
    assert cfg["certify_window_headers"] == default_window(100) == 327
    assert -(-cfg["lite_headers"] // 327) == 13
    for key in ("stake", "stake_changes", "membership_changes",
                "signers_per_commit", "wire", "transition_rule",
                "certify_window_headers"):
        assert len(cfg["assumed"][key]) > 40
    assert "DEPARTURE" in cfg["assumed"]["transition_rule"]
    assert "VerifyCommitAny" in cfg["assumed"]["transition_rule"]
    assert len(cfg["guarantees"]) == 4 and "1/3" in cfg["guarantees"][1]
    assert "nothing on the device" in cfg["chip_layout"]
    assert cfg["rehearsal"] == {
        "validators": 8, "validator_cap": 8, "signers_per_commit": 8,
        "lite_headers": 48, "stake_changes": 12, "membership_changes": 4,
        "certify_window_headers": 8}
    assert len(cfg["source"]) <= 200


# ---------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def notes():
    """The run's notes, kept beside the line."""
    from benchmark.harness import Harness
    kept, note = [], Harness.note

    def keeping(self, kind, **fields):
        kept.append({"bench": kind, **fields})
        return note(self, kind, **fields)
    Harness.note = keeping
    yield kept
    Harness.note = note


def test_the_rehearsal_is_correct_and_reports_end_to_end(notes):
    del notes[:]
    line = rehearse(CELL, seed=2**31 + 40)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"headers_per_s", "setup_s"}
    assert line["metrics"]["headers_per_s"]["unit"] == "headers/s"
    assert all(v > 0 for v in values(line).values())
    assert line["attempted"] % 48 == 0 and line["attempted"] >= 96
    checks = {n["check"]: n for n in notes if n["bench"] == "check"}
    assert set(checks) == {
        "genuine_headers_refused", "signatures_not_verified",
        "dispatch_windows_off_a_pass", "reference_short_of_the_chain",
        "passes_ending_elsewhere_than_the_reference",
        "device_signatures_differing_from_openssl",
        "tampered_chains_not_refused_as_the_reference_does"}
    assert all(c["ok"] and c["limit"] == 0 for c in checks.values())
    ref, = [n for n in notes if n["bench"] == "reference"]
    assert (ref["height"], ref["changes"], ref["refused_at"]) == (
        48, 16, None)
    tampered = {n["case"]: n for n in notes if n["bench"] == "tampered"}
    assert {c: n["refused_for"] for c, n in tampered.items()} == {
        "flipped_signature": "signature", "forged_header": "signature",
        "wrong_validators": "validators_hash",
        "hostile_transition": "endorsement"}
    for n in tampered.values():
        assert n["program_trusts"] == n["reference_trusts"] == \
            n["height"] - 1


def test_the_traced_rehearsal_reports_the_pass_by_leg():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(CELL, trace=True)
    assert line["correct"] is True
    got = values(line)
    # no device here: what a trace or a device counter feeds is left out
    assert NEW | (BY_LEG - {"follow_predecomp_reuse_share"}) <= set(got)
    assert not [m for m in got if m.endswith(
        ("kernel_busy_share", "kernel_sigs_per_s", "device_idle_share"))]
    assert got["lite_compiles_in_window"] == 0
    assert 0 < got["follow_transition_share"] < got["follow_check_share"]
    assert 0 < got["follow_sethash_share"] < got["follow_collect_share"]
    legs = sum(got[k] for k in ("follow_collect_share", "follow_wait_share",
                                "follow_check_share"))
    assert 50 < legs <= 100.5


@pytest.mark.parametrize("control", ["accept_all", "truncate"])
def test_a_verifier_that_checks_less_is_not_correct(control):
    assert rehearse(CELL, control=control)["correct"] is False


def test_a_follower_that_skips_the_endorsement_is_not_correct(monkeypatch):
    from tendermint_tpu.types.validator_set import ValidatorSet
    monkeypatch.setattr(ValidatorSet, "check_endorsement",
                        lambda self, power, extra_ok=(): None)
    assert rehearse(CELL)["correct"] is False


def test_a_window_a_segment_is_not_correct(monkeypatch):
    """A follower that cuts its batch wherever the set moves certifies
    the same headers and dispatches more windows than the chain has."""
    from tendermint_tpu.lite.certifier import ContinuousCertifier
    whole = ContinuousCertifier.advance_many

    def by_segment(self, fcs, window=None):
        lo = 0
        for hi in range(1, len(fcs) + 1):
            if hi == len(fcs) or fcs[hi].validators is not fcs[lo].validators:
                whole(self, fcs[lo:hi], window)
                lo = hi
    monkeypatch.setattr(ContinuousCertifier, "advance_many", by_segment)
    assert rehearse(CELL)["correct"] is False


def test_a_program_without_the_entry_fails_at_once(monkeypatch):
    from tendermint_tpu.lite.certifier import ContinuousCertifier
    monkeypatch.delattr(ContinuousCertifier, "advance_many")
    with pytest.raises(RuntimeError, match="no advance_many"):
        rehearse(CELL)


def test_a_program_without_the_spans_leaves_the_new_metrics_out(monkeypatch):
    """The parent commit: no such span in its catalogue, no such
    family in its registry. The readers return nothing and do not
    raise."""
    from types import SimpleNamespace
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    man = Manifest(REPO)
    r = SimpleNamespace(window=(0.0, 1.0), passes=[SimpleNamespace(
        start=0.0, seconds=1.0)])
    spans = {k: v for k, v in trace.SPANS.items()
             if k not in ("lite.transition", "lite.sethash")}
    monkeypatch.setattr(trace, "SPANS", spans)
    names = [n for n in telemetry.REGISTRY.names()
             if not n.startswith("lite_")]
    monkeypatch.setattr(telemetry.REGISTRY, "names", lambda: names)
    for name in NEW:
        assert man.reader(name).read(r) is None


# ------------------------------------------------------- seeds and sizes

def test_a_seed_changes_contents_and_never_sizes():
    from benchmark.churnchain import MEMBERSHIP, STAKE, ChurnChain
    chains = [ChurnChain(seed, 24, 5, 6, 2, sign="host")
              for seed in (3, 2**31 + 77, 3)]
    a, b, again = chains
    assert a.wire == again.wire and a.valsets_wire == again.valsets_wire
    assert a.wire != b.wire and a.chain_id != b.chain_id
    for c in chains:
        kinds = list(c.change_at.values())
        assert (kinds.count(STAKE), kinds.count(MEMBERSHIP)) == (6, 2)
        assert len(c.valsets_wire) == 9 and c.n_sigs == 24 * 5
        assert len(c.seed_of) == 7 and min(c.change_at) >= 2
    assert [len(w) for w in a.wire] == [len(w) for w in b.wire]
