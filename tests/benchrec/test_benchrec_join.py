"""The cell `chain_100v_join.fastsync_churn`: its entries in the
manifest, found by name; its configuration as the source has it, with
what was cut and what was assumed; its rehearsal on the CPU (8
validators, 48 blocks, 16 changes of set, windows of 8, host-verified),
correct, reporting its metrics, every tampered copy refused; the
controls that have to come out as not correct; and a program that lacks
what the cell reads."""

import json

import pytest

from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

CELL = "chain_100v_join.fastsync_churn"
CONFIG = "chain_100v_join"
SYNC = "chain_64v.fastsync_5ktx"

# what each new reader reads of the program
NEW = {"join_reverified_share": ("program_counter", "sync window engine"),
       "join_reverify_share": ("program_span", "verifier"),
       "join_lanes_discarded_share": ("program_counter", "verifier"),
       "join_sigs_per_needed": ("program_counter", "verifier"),
       "join_update_share": ("program_span", "apply and Merkle")}
# the readers whose lists a test pins to their cell: the constant-set
# cell's (those that read 0.5% of a pass or more here), and the single
# commit's three that split `sync.reverify`
COMMIT = "commit_10kv.verify_commit"
PINNED_TO = {"sync_parts_share": SYNC, "sync_store_share": SYNC,
             "program_decode_share": SYNC, "apply_validate_share": SYNC,
             "apply_save_share": SYNC, "sync_gc_pause_share": SYNC,
             "vc_collect_share": COMMIT, "vc_wait_share": COMMIT,
             "vc_check_share": COMMIT}
NAMESAKES = {"join_" + n for n in PINNED_TO}
SHARED = {"window_collect_share", "wire_decode_share", "apply_ms_per_block",
          "merkle_native_share", "verify_wall_share", "sigs_on_device_share",
          "pad_waste_share", "kernel_busy_share", "kernel_sigs_per_s",
          "device_idle_share", "device_peak_mem_MB", "compiles_in_window",
          "setup_compile_s"}
# what only a chip's trace or memory counter feeds
DEVICE_FED = {"kernel_busy_share", "kernel_sigs_per_s", "device_idle_share",
              "device_peak_mem_MB", "pad_waste_share"}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


# ------------------------------------------------------------ the manifest

def test_the_cell_and_its_configuration_are_declared():
    doc = manifest()
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="fastsync_churn",
                        chips=1)
    assert len(cell["why"]) <= 200 and "re-verified" in cell["why"]
    cfg, = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert cfg["file"] == "benchmark/configs/chain_100v_join.json"
    assert cfg["reduced"] == ["sync_blocks"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for part in ("blockchain/reactor.go", "state/execution.go:286-338",
                 "val: txs", "configs[3]", "Cosmos Hub"):
        assert part in cfg["source"]
    rates = {m["name"]: m for m in doc["end_to_end"]}
    assert {SYNC, CELL} <= set(rates["commits_per_s"]["workloads"])
    assert rates["commits_per_s"]["bound"] == 0.14
    assert CELL not in rates["headers_per_s"]["workloads"]
    man = Manifest(REPO)
    assert {m["name"] for m in man.metrics(CELL, "end_to_end")} == {
        "commits_per_s", "setup_s"}
    assert man.traffic(cell)["driver"] == "sync_join"
    assert man.driver("sync_join").run
    # one configuration and one cell, both last in their lists
    assert doc["workloads"][-1] is cell and doc["configs"][-1] is cfg


@pytest.mark.parametrize("name", sorted(set(NEW) | NAMESAKES))
def test_a_metric_of_its_own_lists_this_cell_alone(name):
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    assert m["workloads"] == [CELL] and m["moves"] == "commits_per_s"
    reader = Manifest(REPO).reader(name)
    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    if name in NEW:
        assert (m["source"], m["layer"]) == NEW[name]
        assert m["better"] == "lower"
        assert m["unit"] == ("ratio" if name == "join_sigs_per_needed"
                             else "%")
    else:                   # the pinned cell's reader, and entry
        twin = name[len("join_"):]
        assert reader.read is Manifest(REPO).reader(twin).read
        t, = [x for x in manifest()["per_layer"] if x["name"] == twin]
        assert t["workloads"] == [PINNED_TO[twin]]
        assert {k: m[k] for k in ("unit", "better", "source", "layer")} == {
            k: t[k] for k in ("unit", "better", "source", "layer")}


@pytest.mark.parametrize("name", sorted(SHARED))
def test_a_shared_reader_lists_this_cell_too(name):
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    assert m["workloads"][:1] == [SYNC] and m["workloads"][-1] == CELL
    assert m["moves"] == Manifest(REPO).reader(name).MOVES == "commits_per_s"


def test_the_cells_per_layer_metrics_are_these():
    got = {m["name"] for m in Manifest(REPO).metrics(CELL, "per_layer")}
    assert got == set(NEW) | NAMESAKES | SHARED
    names = [m["name"] for m in manifest()["per_layer"]]
    assert set(names[-len(NEW) - len(NAMESAKES):]) == set(NEW) | NAMESAKES
    assert len(names) <= 128


def test_every_span_and_counter_a_new_reader_names_is_the_programs():
    import os
    import re
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain import reactor   # declares the families
    from tendermint_tpu.telemetry.trace import SPANS
    from benchmark.drivers import sync_join
    spans = set()
    for name in NEW:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               name + ".py")) as f:
            body = f.read().split('"""', 2)[2]
        spans |= set(re.findall(r'"((?:sync|apply)\.[a-z]+)"', body))
    assert spans == {"sync.reverify", "apply.update"} <= set(SPANS)
    assert SPANS["sync.reverify"] == NEW["join_reverify_share"][1]
    assert SPANS["apply.update"] == NEW["join_update_share"][1]
    families = {family for family, _hows in sync_join._FAMILIES}
    assert families == {"sync_commits_total", "sync_lanes_total"}
    assert families <= set(telemetry.REGISTRY.names())
    assert telemetry.REGISTRY.get("sync_commits_total") is reactor._m_commits
    assert telemetry.REGISTRY.get("sync_lanes_total") is reactor._m_lanes


def test_the_configuration_is_the_sources_with_its_cuts_named():
    from tendermint_tpu.blockchain.reactor import VERIFY_WINDOW
    cfg = Manifest(REPO).config(CONFIG)
    with open(f"{REPO}/benchmark/configs/net_100v.json") as f:
        net = json.load(f)
    with open(f"{REPO}/benchmark/configs/chain_100v_churn.json") as f:
        churn = json.load(f)
    with open(f"{REPO}/benchmark/configs/chain_64v.json") as f:
        c64 = json.load(f)
    assert cfg["validators"] == cfg["validator_cap"] == 100
    assert cfg["signers_per_commit"] == 100
    assert cfg["stake_scale"] == net["stake_scale"] == churn["stake_scale"]
    assert "1000000 // (r + 2)" in cfg["stake"]
    assert set(cfg["reduced"]) == {"sync_blocks"}
    assert cfg["sync_blocks"] == 1024 == 4 * cfg["verify_window_blocks"]
    # chain_100v_churn's rates: a change of stake in one height of four,
    # of membership in one of 64
    assert (cfg["stake_changes"], cfg["membership_changes"]) == (256, 16)
    assert cfg["sync_blocks"] * churn["stake_changes"] == \
        churn["lite_headers"] * cfg["stake_changes"]
    assert cfg["sync_blocks"] * churn["membership_changes"] == \
        churn["lite_headers"] * cfg["membership_changes"]
    assert cfg["verify_window_blocks"] == VERIFY_WINDOW == \
        c64["verify_window_blocks"]
    assert (cfg["tx_bytes"], cfg["key_cycle_heights"]) == (
        c64["tx_bytes"], c64["key_cycle_heights"])
    assert cfg["txs_per_block"] == 16 and cfg["app"] == "kvstore"
    for key in ("stake", "stake_changes", "stake_change_size",
                "membership_changes", "one_delta_a_block",
                "signers_per_commit", "txs_per_block", "tx_bytes", "peer",
                "stores", "verify_window_blocks"):
        assert len(cfg["assumed"][key]) > 40
    assert "val:" in cfg["validator_changes"]
    assert "h + 1" in cfg["validator_changes"]
    assert len(cfg["guarantees"]) == 5
    assert "AT ITS HEIGHT" in cfg["guarantees"][0]
    assert "validators_hash" in cfg["guarantees"][1]
    assert "after a change of set" in cfg["guarantees"][2]
    assert "the stream" in cfg["chip_layout"]
    assert cfg["rehearsal"] == {
        "validators": 8, "validator_cap": 8, "signers_per_commit": 8,
        "sync_blocks": 48, "stake_changes": 12, "membership_changes": 4,
        "txs_per_block": 4, "tx_bytes": 64, "key_cycle_heights": 8,
        "verify_window_blocks": 8}
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


CHECKS = {"blocks_not_applied", "stored_blocks_differing",
          "reference_short_of_the_chain",
          "app_hashes_differing_from_plain_reference",
          "validators_hashes_differing_from_plain_reference",
          "final_set_differing_from_plain_reference",
          "signatures_short_of_one_a_validator_a_block",
          "forged_precommit_not_refused_at_its_height",
          "departed_key_signs_for_joiner_not_refused_at_its_height",
          "val_tx_cut_not_refused_at_its_height"}


@pytest.mark.parametrize("seed", [7, 2**31 + 42])
def test_the_rehearsal_is_correct_and_reports_end_to_end(notes, seed):
    del notes[:]
    line = rehearse(CELL, seed=seed)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"commits_per_s", "setup_s"}
    assert line["metrics"]["commits_per_s"]["unit"] == "commits/s"
    assert all(v > 0 for v in values(line).values())
    assert line["attempted"] % 48 == 0 and line["attempted"] >= 96
    checks = {n["check"]: n for n in notes if n["bench"] == "check"}
    assert set(checks) == CHECKS
    assert all(c["ok"] and c["limit"] == 0 for c in checks.values())
    ref, = [n for n in notes if n["bench"] == "reference"]
    assert (ref["height"], ref["refused_at"], ref["openssl_commits"]) == (
        48, None, 8)
    # every control read above 0: each tampered copy lost blocks, at a
    # height with a change of set below it, to program and reference
    tampered = {n["case"]: n for n in notes if n["bench"] == "tampered"}
    assert {c: n["refused_for"] for c, n in tampered.items()} == {
        "forged_precommit": "signature",
        "departed_key_signs_for_joiner": "signature",
        "val_tx_cut": "validators_hash"}
    for case, n in tampered.items():
        assert n["blocks_refused"] > 0 and n["changes_below"] > 0
        assert n["applied"] == n["reference_applied"] == n["height"] - 1
        assert n["punished"] == (case != "val_tx_cut")
    warm, = [n for n in notes if n["bench"] == "warm_pass"]
    assert warm["seconds"] > 0


def test_the_traced_rehearsal_reports_every_new_metric():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(CELL, trace=True)
    assert line["correct"] is True
    got = values(line)
    # no device here: what a trace or a device counter feeds is left out
    assert set(got) == (set(NEW) | NAMESAKES | SHARED) - DEVICE_FED
    assert got["compiles_in_window"] == 0
    # the set moves 16 times in 48 blocks: nearly every block is judged
    # twice, and the lanes verified for its window are thrown away
    assert 60 < got["join_reverified_share"] < 100
    assert got["join_lanes_discarded_share"] == pytest.approx(
        got["join_reverified_share"])
    # every block's lanes once in its window's batch, and once more for
    # each block re-verified
    assert got["join_sigs_per_needed"] == pytest.approx(
        1 + got["join_reverified_share"] / 100)
    assert 0 < got["join_update_share"] < got["join_reverify_share"] < 100
    # `commit.*` fires nowhere in a pass but inside `sync.reverify`
    # (apply trusts a fast-synced block's last commit): its three legs
    # are most of that span and no more than it
    legs = [got[f"join_vc_{leg}_share"]
            for leg in ("collect", "wait", "check")]
    assert all(x > 0 for x in legs)
    assert 0.5 * got["join_reverify_share"] < sum(legs) <= \
        got["join_reverify_share"]


@pytest.mark.parametrize("control", ["accept_all", "truncate"])
def test_a_verifier_that_checks_less_is_not_correct(notes, control):
    del notes[:]
    assert rehearse(CELL, control=control)["correct"] is False
    failed = {n["check"] for n in notes
              if n["bench"] == "check" and not n["ok"]}
    if control == "accept_all":
        assert {"forged_precommit_not_refused_at_its_height",
                "departed_key_signs_for_joiner_not_refused_at_its_height",
                "signatures_short_of_one_a_validator_a_block"} <= failed
    # a header that names another set is refused whatever the verifier says
    assert "val_tx_cut_not_refused_at_its_height" not in failed


def test_a_node_that_keeps_stale_verdicts_does_not_get_through_a_join(
        monkeypatch):
    """The temptation the next perf_opt must not give in to: taking the
    window's pooled verdicts although the set has moved. The lanes of a
    block above a join were verified under the keys of the set before
    it."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    class AnyHash:
        def __eq__(self, other):
            return True
    collect = BlockchainReactor._collect_window

    def collected_under_whatever_set(self, skip):
        out = collect(self, skip)
        return out and (out[0], out[1], AnyHash(), out[3])
    monkeypatch.setattr(BlockchainReactor, "_collect_window",
                        collected_under_whatever_set)
    with pytest.raises(RuntimeError, match="a warm pass applied"):
        rehearse(CELL)


def test_a_program_with_another_window_fails_at_once(monkeypatch):
    from benchmark.harness import Harness
    from tendermint_tpu.blockchain import reactor
    monkeypatch.setattr(reactor, "VERIFY_WINDOW", 128)
    h = Harness(REPO, CELL, 1, 0.5, False)
    with pytest.raises(RuntimeError, match="verify window is 128"):
        Manifest(REPO).driver("sync_join").run(h)


def test_a_program_without_the_spans_leaves_the_new_metrics_out(monkeypatch):
    """The parent commit: no such span in its catalogue, no such
    family in its registry. The driver counts nothing, the readers
    return nothing and do not raise; the one reading the parent has too
    stays."""
    from types import SimpleNamespace
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    from benchmark.drivers import sync_join
    man = Manifest(REPO)
    spans = {k: v for k, v in trace.SPANS.items()
             if k not in ("sync.reverify", "apply.update")}
    monkeypatch.setattr(trace, "SPANS", spans)
    names = [n for n in telemetry.REGISTRY.names()
             if n not in ("sync_commits_total", "sync_lanes_total")]
    monkeypatch.setattr(telemetry.REGISTRY, "names", lambda: names)
    assert sync_join.program_counts() == {}
    # one family without the other is no parent: a counter was renamed
    monkeypatch.setattr(telemetry.REGISTRY, "names",
                        lambda: names + ["sync_lanes_total"])
    with pytest.raises(RuntimeError, match="tm_sync_commits_total"):
        sync_join.program_counts()
    r = SimpleNamespace(
        window=(0.0, 1.0), passes=[SimpleNamespace(start=0.0, seconds=1.0)],
        counters={"verifier.sigs": 768.0, "join.needed_sigs": 384.0})
    for name in NEW:
        want = 2.0 if name == "join_sigs_per_needed" else None
        assert man.reader(name).read(r) == want


# ------------------------------------------------------- seeds and sizes

def test_a_seed_changes_contents_and_never_sizes():
    from benchmark.joinchain import MEMBERSHIP, STAKE, JoinChain
    chains = [JoinChain(seed, 24, 5, 6, 2, 3, 48, 8)
              for seed in (3, 2**31 + 77, 3)]
    a, b, again = chains
    assert a.wire == again.wire and a.genesis_wire == again.genesis_wire
    assert a.wire != b.wire and a.gen.chain_id != b.gen.chain_id
    for c in chains:
        kinds = list(c.change_at.values())
        assert (kinds.count(STAKE), kinds.count(MEMBERSHIP)) == (6, 2)
        assert len(c.wire) == 25 and c.n_sigs == 24 * 5
        assert len(c.joined_at) == 2 and min(c.change_at) >= 2
        n_val_txs = sum(tx.startswith(b"val:") for raw in c.wire
                        for tx in map(bytes.fromhex,
                                      json.loads(raw)["data"]["txs"]))
        assert n_val_txs == 6 + 2 * 2
    assert [len(json.loads(w)["data"]["txs"]) for w in a.wire] == \
        [3 + {STAKE: 1, MEMBERSHIP: 2}.get(a.change_at.get(h), 0)
         for h in range(1, 25)] + [0]
