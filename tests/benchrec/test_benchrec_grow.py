"""The cell `chain_grow_join.fastsync_grow`: its entries in the manifest,
found by name; the deployment `chain_grow_join` as its sources have it, with what
was cut and what was assumed; benchmark/growchain.py's pass against the
file's counts, the program's serial executor and the plain reference;
the grow cell's rehearsal on the CPU (6 validators growing to 12, 48
blocks, windows of 8, host-verified), correct, every tampered copy
refused at its height and the control accepted; its two readers; the
controls that have to come out as not correct; and a program that lacks
what the cell reads. (The owed cell `net_4v_kvstore.writes_burst` is in
test_benchrec_burst.py.)"""

import json

import pytest

from benchmark.manifest import Manifest
from benchrec_util import REPO, manifest, rehearse

CELL = "chain_grow_join.fastsync_grow"
CONFIG = "chain_grow_join"
JOIN = "chain_100v_join.fastsync_churn"
SYNC = "chain_64v.fastsync_5ktx"

NEW = {"grow_resized_share": ("program_counter", "sync window engine",
                              "higher"),
       "grow_judge_share": ("program_span", "verifier", "lower")}
# accepted entries the cell lists, each under its own name
LISTED = {"window_collect_share", "wire_decode_share", "apply_ms_per_block",
          "merkle_native_share", "verify_wall_share", "sigs_on_device_share",
          "pad_waste_share", "kernel_busy_share", "kernel_sigs_per_s",
          "device_idle_share", "device_peak_mem_MB", "compiles_in_window",
          "setup_compile_s", "sync_gc_pause_share", "join_reverified_share",
          "join_live_judged_share", "join_lanes_discarded_share",
          "join_sigs_per_needed", "join_update_share", "sync_parts_share",
          "sync_store_share", "program_decode_share", "apply_validate_share",
          "apply_save_share"}
DEVICE_FED = {"kernel_busy_share", "kernel_sigs_per_s", "device_idle_share",
              "device_peak_mem_MB", "pad_waste_share"}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


# ------------------------------------------------------------ the manifest

def test_the_cell_and_its_configuration_are_declared():
    doc = manifest()
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="fastsync_grow", chips=1)
    assert len(cell["why"]) <= 200 and "64 -> 100" in cell["why"]
    assert "paired by address" in cell["why"]
    assert "verified again" in cell["why"]
    cfg, = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert cfg["file"] == "benchmark/configs/chain_grow_join.json"
    assert cfg["reduced"] == ["sync_blocks"]
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    for part in ("state/execution.go:246 updateValidators", ":286-338",
                 "TestReactorValidatorSetChanges", "val: txs",
                 "Cosmos Hub cap of 100"):
        assert part in cfg["source"]
    assert cfg["source"] == Manifest(REPO).config(CONFIG)["source"]
    rates = {m["name"]: m for m in doc["end_to_end"]}
    assert {SYNC, JOIN, CELL} <= set(rates["commits_per_s"]["workloads"])
    assert rates["commits_per_s"]["bound"] == 0.14
    man = Manifest(REPO)
    assert {m["name"] for m in man.metrics(CELL, "end_to_end")} == {
        "commits_per_s", "setup_s"}
    assert man.traffic(cell)["driver"] == "sync_grow"
    assert man.driver("sync_grow").run


def test_the_cells_per_layer_metrics_are_these_and_no_namesake():
    got = {m["name"] for m in Manifest(REPO).metrics(CELL, "per_layer")}
    assert got == LISTED | set(NEW)
    # the ORIGINALS, never their `join_` twins
    assert not [n for n in got if n.startswith(("join_sync_", "join_apply_",
                                                "join_program_", "join_vc_"))]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_reader_lists_both_join_cells(name):
    m, = [x for x in manifest()["per_layer"] if x["name"] == name]
    source, layer, better = NEW[name]
    assert m == {"name": name, "unit": "%", "better": better,
                 "source": source, "layer": layer, "moves": "commits_per_s",
                 "workloads": [CELL, JOIN]}
    reader = Manifest(REPO).reader(name)
    assert (reader.LAYER, reader.MOVES) == (layer, "commits_per_s")


def test_every_span_and_counter_a_new_reader_names_is_the_programs():
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain import reactor   # declares the family
    from tendermint_tpu.telemetry.trace import SPANS
    from benchmark.drivers import sync_grow
    assert SPANS["sync.judge"] == NEW["grow_judge_share"][1]
    assert sync_grow.RESIZED in telemetry.REGISTRY.names()
    assert telemetry.REGISTRY.get(sync_grow.RESIZED) is reactor._m_resized
    assert sync_grow.RESIZED in sync_grow.window_counts()


def test_the_configuration_is_the_sources_with_its_cuts_named():
    from tendermint_tpu.blockchain.reactor import VERIFY_WINDOW
    cfg = Manifest(REPO).config(CONFIG)
    with open(f"{REPO}/benchmark/configs/net_100v.json") as f:
        net = json.load(f)
    with open(f"{REPO}/benchmark/configs/chain_100v_join.json") as f:
        join = json.load(f)
    assert (cfg["genesis_validators"], cfg["validator_cap"]) == (64, 100)
    assert cfg["validator_cap"] == join["validator_cap"]
    assert cfg["genesis_validators"] + cfg["joins"] - cfg["leaves"] == 100
    assert (cfg["joins"], cfg["leaves"], cfg["stake_changes"]) == (40, 4, 256)
    assert cfg["stake_changes"] == join["stake_changes"]
    assert cfg["stake_scale"] == net["stake_scale"] == join["stake_scale"]
    assert "1000000 // (r + 2)" in cfg["stake"] and "64 + k" in cfg["stake"]
    assert set(cfg["reduced"]) == {"sync_blocks"}
    assert cfg["sync_blocks"] == 1024 == 4 * cfg["verify_window_blocks"]
    assert cfg["verify_window_blocks"] == VERIFY_WINDOW
    for key in ("txs_per_block", "tx_bytes", "key_cycle_heights", "app",
                "verify_window_blocks", "sync_blocks"):
        assert cfg[key] == join[key], key
    for key in ("genesis_validators", "joins_and_leaves", "stake",
                "stake_changes", "stake_change_size", "one_delta_a_block",
                "signers_per_commit", "txs_per_block", "tx_bytes", "peer",
                "stores", "verify_window_blocks"):
        assert len(cfg["assumed"][key]) > 40
    assert "launch week" in cfg["assumed"]["joins_and_leaves"]
    assert "val:" in cfg["validator_changes"]
    assert "h + 1" in cfg["validator_changes"]
    assert "an unknown key adds" in cfg["validator_changes"]
    # chain_100v_join's five, word for word, and the one of its own
    assert cfg["guarantees"][:5] == join["guarantees"]
    assert len(cfg["guarantees"]) == 6
    assert "exactly one slot for every member" in cfg["guarantees"][5]
    assert "the stream" in cfg["chip_layout"]
    assert "benchmark/joinref.py as it stands" in cfg["plain_reference"]
    for said in ("104 distinct keys", "301 distinct sets", "every 23 blocks",
                 "about 84,000", "44 equal stretches"):
        assert said in cfg["a_pass"]
    assert cfg["rehearsal"] == {
        "genesis_validators": 6, "validator_cap": 12, "sync_blocks": 48,
        "joins": 8, "leaves": 2, "stake_changes": 10, "txs_per_block": 4,
        "tx_bytes": 64, "key_cycle_heights": 8, "verify_window_blocks": 8}


# --------------------------------------------------------------- the chain

def small_chain(seed, **kw):
    from benchmark.growchain import GrowChain
    return GrowChain(seed, 40, 6, 12, 8, 2, 10, 3, 48, 8, **kw)


@pytest.mark.parametrize("seed", [3, 2**31 + 77])
def test_a_pass_has_the_joins_leaves_and_final_size_it_is_given(seed):
    from benchmark.growchain import JOIN as J, LEAVE as L
    from benchmark.joinchain import STAKE
    from benchmark.drivers.sync_grow import reference_sizes
    chain = small_chain(seed)
    kinds = list(chain.change_at.values())
    assert (kinds.count(J), kinds.count(L), kinds.count(STAKE)) == (8, 2, 10)
    assert len(chain.change_at) == 20 and min(chain.change_at) >= 2
    assert len(chain.joined_at) == 8 and len(chain.left_at) == 2
    sizes = [chain.size_at[h] for h in range(1, 42)]
    assert sizes[0] == 6 and sizes[-1] == 12 and max(sizes) <= 12
    assert chain.n_sigs == sum(sizes[:40]) and len(chain.wire) == 41
    # the set changes size exactly where a join or a leave is in force
    for h in range(1, 41):
        kind = chain.change_at.get(h)
        assert sizes[h] - sizes[h - 1] == {J: 1, L: -1}.get(kind, 0)
    # a joiner enters at the bottom, with the law's power at its rank
    gen = {bytes.fromhex(v["pubkey"]) for v in
           json.loads(chain.genesis_wire)["validators"]}
    joiners = [chain.joined_at[h] for h in sorted(chain.joined_at)]
    assert all(member != joiner and joiner not in gen
               for member, joiner in joiners)
    assert len({j for _m, j in joiners} | gen) == 14
    txs = {h: [bytes.fromhex(t) for t in
               json.loads(chain.wire[h - 1])["data"]["txs"]
               if bytes.fromhex(t).startswith(b"val:")]
           for h in chain.change_at}
    assert all(len(v) == 1 for v in txs.values())
    for k, h in enumerate(sorted(chain.joined_at), start=1):
        assert txs[h] == [b"val:%s/%d" % (
            chain.joined_at[h][1].hex().encode(), 1_000_000 // (6 + k + 2))]
    for h, key in chain.left_at.items():
        assert txs[h] == [b"val:%s/0" % key.hex().encode()]
        assert key not in {j for x, (_m, j) in chain.joined_at.items()
                           if x > h}           # it does not return
    # the plain reference derives the same sizes from the wire alone
    assert reference_sizes(chain.genesis_wire, chain.wire) == sizes
    again = small_chain(seed)
    assert again.wire == chain.wire and again.size_at == chain.size_at


def test_every_seed_offers_the_same_work_in_another_order():
    """One join or leave to each equal stretch of the chain and one
    leave to each equal run of them, so a pass's signatures move by a
    hundredth or two from seed to seed where free draws moved them by
    a twentieth (and `commits_per_s` with them: PERF.md section 6)."""
    from benchmark.growchain import JOIN as J, LEAVE as L
    chains = [small_chain(seed) for seed in (1, 2, 3, 2**31 + 5)]
    for chain in chains:
        resizing = sorted(h for h, k in chain.change_at.items()
                          if k in (J, L))
        edges = [2 + i * 39 // 10 for i in range(11)]
        assert all(lo <= h < hi for h, lo, hi in
                   zip(resizing, edges, edges[1:]))
        kinds = [chain.change_at[h] for h in resizing]
        assert kinds[:5].count(L) == kinds[5:].count(L) == 1
    assert len({tuple(sorted(c.change_at.items())) for c in chains}) == 4
    # at the timed size, from the placement alone (no chain is built)
    import random
    from benchmark.growchain import GrowChain
    cfg = Manifest(REPO).config(CONFIG)
    sigs = []
    for seed in range(2470000511, 2470000523):
        dry = object.__new__(GrowChain)
        dry._rng = random.Random(f"{seed}/join/sets")
        dry._genesis_vals, dry._cap = cfg["genesis_validators"], 100
        dry._leaves = cfg["leaves"]
        at = dry._place_changes(cfg["sync_blocks"], cfg["stake_changes"],
                                cfg["joins"])
        assert len(at) == 300 and 2 <= min(at) and max(at) <= 1024
        size = total = 64
        for h in range(1, 1024):
            size += {J: 1, L: -1}.get(at.get(h), 0)
            assert size <= 100
            total += size
        sigs.append(total)
    assert 83000 < min(sigs) and max(sigs) < 85000
    assert max(sigs) - min(sigs) < 0.02 * min(sigs)


def test_the_cap_is_never_passed_and_a_pass_that_cannot_fit_is_refused():
    from benchmark.growchain import GrowChain
    with pytest.raises(ValueError, match="pass the cap"):
        GrowChain(1, 20, 6, 8, 4, 1, 2, 2, 48, 8)
    # 6 + 6 - 4 = 8 = the cap: the order of joins and leaves is drawn
    # until the set never stands above it
    for seed in range(5):
        chain = GrowChain(seed, 30, 6, 8, 6, 4, 2, 2, 48, 8)
        assert max(chain.size_at.values()) <= 8
        assert chain.size_at[31] == 8


def test_the_plain_reference_agrees_with_the_serial_executor():
    """benchmark/joinref.py as it stands, on a set that grows and
    shrinks: the sets, the app hashes and the final set of `apply_block`
    one block at a time, and each tampered copy refused where the
    program's verify_commit under the set in force refuses it."""
    import sys
    sys.path.insert(0, f"{REPO}/tests")
    from test_fast_sync_churn import serial
    from benchmark import joinref
    from benchmark.growchain import (address_rewritten,
                                     leaver_still_in_commit)
    from benchmark.joinchain import departed_signs_for_joiner
    chain = small_chain(5)
    state, sets, apps = serial(chain)
    ref = joinref.replay(chain.genesis_wire, chain.wire)
    assert (ref.height, ref.refused_at) == (40, None)
    assert ref.validators_hashes == sets and ref.app_hashes == apps
    assert ref.validators == [(v.pubkey, v.voting_power)
                              for v in state.validators.validators]
    at, wire = leaver_still_in_commit(chain, min(chain.left_at))
    ref = joinref.replay(chain.genesis_wire, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        at - 1, at, joinref.COMMIT)
    at, wire = departed_signs_for_joiner(chain, min(chain.joined_at))
    ref = joinref.replay(chain.genesis_wire, wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        at - 1, at, joinref.SIGNATURE)
    cut_at = min(chain.joined_at)
    cut = small_chain(5, cut_val_at=cut_at)
    ref = joinref.replay(chain.genesis_wire, cut.wire)
    assert (ref.height, ref.refused_at, ref.kind) == (
        cut_at, cut_at + 1, joinref.VALIDATORS_HASH)
    at = max(chain.joined_at) + 1
    ref = joinref.replay(chain.genesis_wire,
                         address_rewritten(chain, at, 0, 3))
    assert (ref.height, ref.refused_at) == (at, None)


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


REFUSED = {"forged_precommit": "signature",
           "joiners_vote_signed_by_another_member": "signature",
           "leavers_slot_still_in_the_commit": "commit",
           "join_val_tx_cut": "validators_hash"}
ACCEPTED = "vote_claims_another_members_address"
CHECKS = {"chain_differing_from_the_configuration", "blocks_not_applied",
          "stored_blocks_differing", "reference_short_of_the_chain",
          "app_hashes_differing_from_plain_reference",
          "validators_hashes_differing_from_plain_reference",
          "final_set_differing_from_plain_reference",
          "signatures_short_of_one_a_member_a_block",
          ACCEPTED + "_not_accepted_whole"} | {
              name + "_not_refused_at_its_height" for name in REFUSED}


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
    assert (ref["height"], ref["refused_at"], ref["openssl_commits"],
            ref["final_size"]) == (48, None, 8, 12)
    tampered = {n["case"]: n for n in notes if n["bench"] == "tampered"}
    assert {c: n["refused_for"] for c, n in tampered.items()} == dict(
        REFUSED, **{ACCEPTED: None})
    for case, n in tampered.items():
        if case == ACCEPTED:
            assert n["applied"] == n["reference_applied"] == n["height"]
            assert n["blocks_refused"] == 0 and not n["punished"]
        else:
            assert n["applied"] == n["reference_applied"] == n["height"] - 1
            assert n["blocks_refused"] > 0 and n["punished"]
    # the forged height and the control's lie above a change of size
    assert tampered["forged_precommit"]["set_size"] != 6
    assert tampered[ACCEPTED]["set_size"] != 6
    made, = [n for n in notes if n["bench"] == "chain"]
    assert made["sizes"][0] == 6 and made["sizes"][-1] == 12


def test_the_traced_rehearsal_reads_the_two_new_metrics_in_both_cells():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(CELL, trace=True)
    assert line["correct"] is True
    got = values(line)
    # no device here: what a trace or a device counter feeds is left out
    assert (LISTED | set(NEW)) - DEVICE_FED <= set(got)
    # most blocks lie above a join their window had not seen, none is
    # verified whole again, and the joiners' lanes alone are lost
    assert 50 < got["grow_resized_share"] <= got["join_live_judged_share"]
    assert got["join_live_judged_share"] <= 100
    assert got["join_reverified_share"] == 0
    assert 0 < got["join_lanes_discarded_share"] < 40
    assert 1 < got["join_sigs_per_needed"] < 1.4
    assert 0 < got["grow_judge_share"] < 100
    # the set at its cap: the judge runs, no commit is of another size.
    # Its driver does not take the counter at the window's edges, so the
    # reader reads the families whole, as a process of one cell has them
    from tendermint_tpu.blockchain import reactor
    for child in (reactor._m_resized._implicit,
                  reactor._m_commits.labels("batched"),
                  reactor._m_commits.labels("reverified")):
        child.value = 0.0
    telemetry.TRACER.clear()
    got = values(rehearse(JOIN, trace=True))
    assert got["grow_resized_share"] == 0
    assert 0 < got["grow_judge_share"] < 100


@pytest.mark.parametrize("control", ["accept_all", "truncate"])
def test_a_verifier_that_checks_less_is_not_correct(notes, control):
    del notes[:]
    assert rehearse(CELL, control=control)["correct"] is False
    failed = {n["check"] for n in notes
              if n["bench"] == "check" and not n["ok"]}
    if control == "accept_all":
        assert {"forged_precommit_not_refused_at_its_height",
                "joiners_vote_signed_by_another_member"
                "_not_refused_at_its_height",
                "signatures_short_of_one_a_member_a_block"} <= failed
    # a commit of another size is refused whatever the verifier says
    assert "leavers_slot_still_in_the_commit_not_refused_at_its_height" \
        not in failed
    assert "join_val_tx_cut_not_refused_at_its_height" not in failed


def test_a_judge_that_counts_slots_no_more_is_not_correct(monkeypatch):
    """The size rule of the live judge taken away: the commit that still
    carries the leaver's slot is no longer refused where it stands."""
    from tendermint_tpu.types.validator_set import ValidatorSet
    judge = ValidatorSet.check_commit_lanes

    def lenient(self, commit, lanes, ok, for_block, verifier):
        if len(self.validators) != len(commit.precommits):
            return 0
        return judge(self, commit, lanes, ok, for_block, verifier)
    monkeypatch.setattr(ValidatorSet, "check_commit_lanes", lenient)
    assert rehearse(CELL)["correct"] is False


def test_a_program_with_another_window_fails_at_once(monkeypatch):
    from benchmark.harness import Harness
    from tendermint_tpu.blockchain import reactor
    monkeypatch.setattr(reactor, "VERIFY_WINDOW", 128)
    h = Harness(REPO, CELL, 1, 0.5, False)
    with pytest.raises(RuntimeError, match="verify window is 128"):
        Manifest(REPO).driver("sync_grow").run(h)


def test_a_program_without_the_span_and_the_counter_leaves_them_out(
        monkeypatch):
    """The parent commit: no `sync.judge` in its catalogue, no
    `tm_sync_resized_total` in its registry. The driver counts nothing
    of it, the two readers return nothing and do not raise."""
    from types import SimpleNamespace
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    from benchmark.drivers import sync_grow
    man = Manifest(REPO)
    monkeypatch.setattr(trace, "SPANS", {
        k: v for k, v in trace.SPANS.items() if k != "sync.judge"})
    names = [n for n in telemetry.REGISTRY.names()
             if n != sync_grow.RESIZED]
    monkeypatch.setattr(telemetry.REGISTRY, "names", lambda: names)
    counts = sync_grow.window_counts()
    assert sync_grow.RESIZED not in counts
    assert "sync_commits_total.batched" in counts
    r = SimpleNamespace(
        window=(0.0, 1.0), passes=[SimpleNamespace(start=0.0, seconds=1.0)],
        counters={"sync_commits_total.batched": 96.0,
                  "sync_commits_total.reverified": 0.0})
    for name in NEW:
        assert man.reader(name).read(r) is None
    r.counters[sync_grow.RESIZED] = 72.0
    assert man.reader("grow_resized_share").read(r) == 75.0
