"""The cell `commit_10kv.verify_commit`: its entries in BENCHMARK.json,
the commits its builder makes from a seed, its rehearsal at toy sizes on
the CPU (24 validators cut at 16, so a commit still has a tail chunk in
the driver's arithmetic; batches verify on the host), the controls and
broken programs that have to come out as not correct, and what its
readers do on a program that lacks the `commit.*` spans."""

import random
from types import SimpleNamespace

import pytest

from benchmark import commitref, commits
from benchmark.drivers import verify_commit as driver
from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

CELL = "commit_10kv.verify_commit"
SYNC = "chain_64v.fastsync_5ktx"
# the cell's own readers ...
SPAN_FED = {"vc_collect_share", "vc_wait_share", "vc_check_share",
            "vc_prep_share", "vc_predecomp_share", "vc_enqueue_share",
            "vc_starved_share"}
COUNTED = {"vc_commit_p50_ms", "vc_predecomp_reuse_share",
           "vc_h2d_bytes_per_sig"}
# ... and fast-sync's metrics of the layers this cell runs too, which
# move the same `commits_per_s`: reported here under their own names
SHARED = {"sigs_on_device_share", "pad_waste_share", "kernel_busy_share",
          "kernel_sigs_per_s", "device_idle_share", "device_peak_mem_MB",
          "compiles_in_window", "setup_compile_s"}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


# ------------------------------------------------------------ the manifest

def test_the_cell_and_its_metrics_are_declared():
    """By name, wherever in their lists later entries leave them."""
    doc = manifest()
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "commit_10kv", "verify_commit", 1)
    config, = [c for c in doc["configs"] if c["name"] == "commit_10kv"]
    assert config["reduced"] == []
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert {SYNC, CELL} <= set(e2e["commits_per_s"]["workloads"])
    assert e2e["commits_per_s"]["bound"] == 0.14
    by = {m["name"]: m for m in doc["per_layer"]}
    man = Manifest(REPO)
    assert {m["name"] for m in man.metrics(CELL, "per_layer")} == \
        SPAN_FED | COUNTED | SHARED
    assert {m["name"] for m in man.metrics(CELL, "end_to_end")} == {
        "commits_per_s", "setup_s"}
    for name in SPAN_FED | COUNTED:
        m, reader = by[name], man.reader(name)
        assert m["workloads"] == [CELL] and m["moves"] == "commits_per_s"
        assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    for name in SHARED:                 # one name for one reading
        assert {SYNC, CELL} <= set(by[name]["workloads"])
        assert by[name]["moves"] == man.reader(name).MOVES == "commits_per_s"
        assert "vc_" + name not in by


def test_the_entries_before_this_cell_are_as_they_were():
    """PR 25's entry, which its own test looks for at `per_layer[-1]`
    (tests/conftest.py), held here by name with that test's other
    assertions; and no metric of another cell's end-to-end metric
    names this cell."""
    from benchmark.metrics import lite_predecomp_reuse_share as reader
    doc = manifest()
    lite, name = "chain_64v.lite_certify", "lite_predecomp_reuse_share"
    m, = [x for x in doc["per_layer"] if x["name"] == name]
    assert m == {"name": name, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "verifier",
                 "moves": "headers_per_s", "workloads": [lite]}
    man = Manifest(REPO)
    assert man.reader(name) is reader
    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    assert name in [x["name"] for x in man.metrics(lite, "per_layer")]
    assert all(m["moves"] == "commits_per_s" for m in doc["per_layer"]
               if CELL in m.get("workloads", ()))


def test_the_configuration_is_the_sources_commit_uncut():
    from tendermint_tpu.models.verifier import BATCH_CHUNK
    from tendermint_tpu.ops.ed25519 import _bucket
    cfg = Manifest(REPO).config("commit_10kv")
    assert cfg["validators"] == cfg["signers_per_commit"] == 10_000
    assert cfg["reduced"] == {} and cfg["guarantees"] and cfg["chip_layout"]
    assert set(cfg["assumed"]) == {"voting_power_each", "vote_timestamps",
                                   "commit_heights", "verify_chunk"}
    assert cfg["verify_chunk"] == BATCH_CHUNK == 8192
    assert cfg["chunks_per_commit"] == [8192, 1808]
    assert sum(cfg["chunks_per_commit"]) == cfg["validators"]
    assert _bucket(1808) == 2048        # the tail's kernel shape
    assert "configs[2]" in cfg["source"] and "VerifyCommit" in cfg["source"]
    assert len(cfg["source"]) <= 200
    # 6,667 of 10,000 equal validators are a quorum and 6,666 are not
    total = cfg["validators"] * cfg["voting_power_each"]
    assert 3 * 6667 * cfg["voting_power_each"] > 2 * total
    assert not 3 * 6666 * cfg["voting_power_each"] > 2 * total


@pytest.mark.parametrize("metric", sorted(SPAN_FED | COUNTED | SHARED))
def test_a_program_without_the_spans_or_a_trace_reads_nothing(
        monkeypatch, metric):
    """What the parent commit, and a run without a device, look like to
    the cell's readers: nothing raises, the metric is left out."""
    from benchmark import program_spans
    from tendermint_tpu.telemetry import trace
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items() if not k.startswith("commit.")})
    monkeypatch.setattr(program_spans, "counter_total", lambda *a: None)
    r = SimpleNamespace(window=(0.0, 10.0), passes=[], counters={},
                        client={}, trace=None, memory_peak_bytes=None,
                        compiles_in_window=0, setup_compile_s=0.0)
    got = Manifest(REPO).reader(metric).read(r)
    if metric in ("compiles_in_window", "setup_compile_s"):
        assert got == 0.0
    else:
        assert got is None


# ---------------------------------------------------- commits from a seed

@pytest.fixture(scope="module")
def toy():
    return commits.CommitSet(2 ** 31 + 5, 24, 3, 10)


def test_commit_sizes_do_not_depend_on_the_seed(toy):
    other = commits.CommitSet(9, 24, 3, 10)
    again = commits.CommitSet(9, 24, 3, 10)
    assert [len(w) for w in toy.wire] == [len(w) for w in other.wire]
    assert len(toy.valset_wire) == len(other.valset_wire)
    assert toy.wire != other.wire and toy.pubkeys != other.pubkeys
    assert (again.wire, again.valset_wire) == (other.wire, other.valset_wire)
    assert len(set(toy.msgs)) == 3 * 24     # no two votes share sign-bytes
    assert len(set(toy.block_ids)) == 3


def test_signing_in_blocks_of_validators_changes_no_byte(toy, monkeypatch):
    monkeypatch.setattr(commits, "SIGN_BLOCK", 7)      # 7 + 7 + 7 + 3
    again = commits.CommitSet(2 ** 31 + 5, 24, 3, 10)
    assert (again.sigs, again.wire) == (toy.sigs, toy.wire)


def test_the_program_decodes_what_the_reference_holds(toy):
    valset, decoded = toy.decode()
    assert [(v.pubkey, v.voting_power) for v in valset.validators] == \
        toy.validators()
    assert [h for _b, h, _c in decoded] == [1, 2, 3]
    for block_id, height, commit in decoded:
        plain = toy.votes(height)
        assert [v.signature for v in commit.precommits] == \
            [v.signature for v in plain]
        assert [v.sign_bytes(toy.chain_id) for v in commit.precommits] == \
            [commitref.sign_bytes(toy.chain_id, v) for v in plain] == \
            [m for _pk, m, _s in toy.items(height)]
        assert commitref.verify_commit(
            toy.chain_id, toy.validators(), toy.block_id(height), height,
            plain) is None
        assert (block_id.hash, block_id.parts.total, block_id.parts.hash) \
            == toy.block_id(height)


def test_the_commit_cases_cover_both_chunks_and_both_verdicts(toy):
    cases = commits.commit_cases(toy, 16, random.Random(3))
    verdicts = {name: commitref.verify_commit(
        toy.chain_id, toy.validators(), bid, h, votes)
        for name, bid, h, votes in cases}
    assert {k: v is None for k, v in verdicts.items()} == {
        "bad_signature_in_first_chunk": False,
        "bad_signature_in_tail_chunk": False,
        "two_thirds_and_no_more": False, "two_thirds_and_one": True,
        "three_tenths_absent": True, "one_vote_short": False,
        "vote_of_another_height": False}
    first, tail = (int(verdicts[k].rsplit(" ", 1)[1]) for k in (
        "bad_signature_in_first_chunk", "bad_signature_in_tail_chunk"))
    assert first < 16 <= tail
    assert verdicts["two_thirds_and_no_more"] == \
        "insufficient voting power: got 160 of 240"
    by = {name: votes for name, _b, _h, votes in cases}
    assert sum(v.block_id == commitref.NIL_BLOCK
               for v in by["two_thirds_and_one"]) == 7
    assert sum(v is None for v in by["three_tenths_absent"]) == 7


def test_tampered_lanes_reach_into_the_tail_chunk_at_the_real_size():
    n, chunk = 10_000, 8192
    fake = SimpleNamespace(
        pubkeys=[b"%032d" % i for i in range(n)],
        items=lambda height: [(b"%032d" % i, b"m%d" % i, bytes(64))
                              for i in range(n)])
    for seed in range(5):
        items, lanes = driver.tampered_commit(fake, 1, chunk,
                                              random.Random(seed))
        assert len(lanes) == len(set(lanes)) == 40
        assert sum(lane >= chunk for lane in lanes) == 10
        broken = [i for i in range(n)
                  if items[i] != (b"%032d" % i, b"m%d" % i, bytes(64))]
        assert broken == lanes


# ---------------------------------------------------------- the rehearsal

def test_end_to_end_line():
    line = rehearse(CELL, seed=2 ** 31 + 11)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"commits_per_s", "setup_s"}
    assert all(v > 0 for v in values(line).values())
    assert line["metrics"]["commits_per_s"]["unit"] == "commits/s"
    assert line["attempted"] >= 6 and line["attempted"] % 3 == 0
    assert line["device"]["rehearsal"] is True


def test_traced_line_has_every_span_fed_metric():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(CELL, trace=True)
    assert line["correct"] is True
    got = values(line)
    assert SPAN_FED <= set(got)
    # no device here: what reads a trace, the device's memory or the
    # device's counters finds nothing and is left out
    assert set(got) == SPAN_FED | {
        "vc_commit_p50_ms", "sigs_on_device_share",
        "compiles_in_window", "setup_compile_s"}
    assert "breakdown" not in line
    assert all(0.0 <= got[k] <= 100.0 for k in SPAN_FED)
    # one thread, one commit at a time: the legs are disjoint
    legs = sum(got[k] for k in SPAN_FED - {"vc_starved_share"})
    assert 50.0 < legs <= 100.5
    assert got["vc_collect_share"] > 0 and got["vc_check_share"] > 0
    assert got["vc_wait_share"] > got["vc_collect_share"]
    # host-verified batches: nothing was enqueued or in flight
    assert got["vc_enqueue_share"] == got["vc_predecomp_share"] == 0.0
    assert got["vc_starved_share"] == 100.0
    assert got["compiles_in_window"] == 0 and got["vc_commit_p50_ms"] > 0


def test_traced_line_on_a_program_without_the_commit_spans(monkeypatch):
    """The parent commit with this cell's files laid over it: a line,
    with the three `commit.*` metrics left out."""
    from tendermint_tpu.telemetry import trace
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items() if not k.startswith("commit.")})
    line = rehearse(CELL, trace=True)
    assert line["correct"] is True
    assert SPAN_FED - set(line["metrics"]) == {
        "vc_collect_share", "vc_wait_share", "vc_check_share"}


@pytest.mark.parametrize("control", ["accept_all", "truncate"])
def test_a_verifier_that_checks_less_is_not_correct(control):
    assert rehearse(CELL, control=control)["correct"] is False


def test_a_program_that_drops_the_tail_chunks_verdicts_is_not_correct(
        monkeypatch):
    from tendermint_tpu.types.validator_set import ValidatorSet
    whole = ValidatorSet.check_commit_results

    def head_only(self, ok, item_power):
        return whole(self, list(ok[:16]) + [True] * (len(ok) - 16),
                     item_power)
    monkeypatch.setattr(ValidatorSet, "check_commit_results", head_only)
    assert rehearse(CELL)["correct"] is False


def test_a_program_that_skips_the_stake_tally_is_not_correct(monkeypatch):
    from tendermint_tpu.types.validator_set import ValidatorSet

    def signatures_only(self, ok, item_power):
        if not all(ok):
            raise ValueError("invalid signature in commit")
    monkeypatch.setattr(ValidatorSet, "check_commit_results",
                        signatures_only)
    assert rehearse(CELL)["correct"] is False


def test_a_driver_whose_commits_are_refused_reports_them_failed(monkeypatch):
    """A program that refuses genuine commits: the passes count them
    failed (the warm pass would stop the run, so it is let through)."""
    from tendermint_tpu.types.validator_set import ValidatorSet
    whole = ValidatorSet.verify_commit
    calls = {"n": 0}

    def refusing(self, chain_id, block_id, height, commit, verifier=None):
        calls["n"] += 1
        if calls["n"] > 3 and height == 2:
            raise ValueError("refused for the test")
        return whole(self, chain_id, block_id, height, commit, verifier)
    monkeypatch.setattr(ValidatorSet, "verify_commit", refusing)
    line = rehearse(CELL)
    assert line["correct"] is False and line["failed"] > 0
