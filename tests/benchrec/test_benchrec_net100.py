"""The cell net_100v.writes_steady: its plain reference
(benchmark/stakeref.py) against the program's rotation and
`verify_commit`, its run's own clock, its six readers on recorded
values, its declaration, and the whole command's path at 7 validators
(2 in this process, 5 in 2 workers) on the CPU."""

import json
import os
import random
import time

import pytest

from benchrec_util import REPO, manifest, rehearse

CELL = "net_100v.writes_steady"
M = manifest()
LAYER = {m["name"]: m for m in M["per_layer"]}
NEW = ["n100_vote_ingest_ms_per_height", "n100_votes_duplicate_share",
       "n100_p2p_msgs_per_height", "n100_vote_batch_sigs_p50",
       "n100_link_delay_p50_ms", "n100_workers_cpu_share"]


# ----------------------------------------------------------- stakeref

def _set(n, seed):
    """A validator set under the deployment's stake law, as the
    program's ValidatorSet and as stakeref's plain triples, with the
    keys."""
    from tendermint_tpu.types import PrivKey
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    rng = random.Random(seed)
    keys = [PrivKey.generate(rng.randbytes(32)) for _ in range(n)]
    powers = [1_000_000 // (r + 2) for r in range(1, n + 1)]
    rng.shuffle(powers)
    vs = ValidatorSet([Validator(k.pubkey.ed25519, p)
                       for k, p in zip(keys, powers)])
    by_addr = {k.pubkey.address: k for k in keys}
    plain = [(v.address, v.pubkey, v.voting_power) for v in vs.validators]
    return vs, plain, [by_addr[v.address] for v in vs.validators]


@pytest.mark.parametrize("n,seed", [(100, 3), (7, 4), (4, 5)])
def test_proposers_are_the_programs_over_1000_heights(n, seed):
    from benchmark import stakeref
    vs, plain, _keys = _set(n, seed)
    rng = random.Random(seed)
    rounds = [rng.choice([0, 0, 0, 0, 1, 2, 5]) for _ in range(1000)]
    want = stakeref.proposers(plain, rounds)
    for h, r in enumerate(rounds):
        at = vs
        if r:
            at = vs.copy()
            at.increment_accum(r)
        assert at.proposer().address == want[h], (h, r)
        vs = vs.copy()
        vs.increment_accum(1)       # the block is applied
    if n == 100:
        # rotation by stake: the largest proposes most, all propose
        counts = {a: want.count(a) for a in set(want)}
        top = stakeref.largest(plain, 1)[0]
        assert counts[top] == max(counts.values()) and len(counts) == 100
        assert 50 <= counts[top] <= 110     # 9.0% of the stake


def test_the_stake_law_is_the_issues():
    from benchmark.drivers.fleet_procs import stake_law
    law = stake_law(100, 1_000_000)
    total = sum(law)
    assert law == sorted(law, reverse=True) and law[0] == 333_333
    assert round(100 * law[0] / total, 1) == 9.0
    assert round(100 * sum(law[:10]) / total) == 43
    assert round(100 * sum(law[:33]) / total) == 71
    assert round(100 * sum(law[-66:]) / total) == 28


def _commit(vs, keys, signers, chain_id="stakeref-test", height=5):
    from tendermint_tpu.types.block import BlockID, Commit, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    votes = []
    for i, v in enumerate(vs.validators):
        if i not in signers:
            votes.append(None)
            continue
        vote = Vote(v.address, i, height, 0, 1_000 + i, VoteType.PRECOMMIT,
                    bid)
        vote.signature = keys[i].sign(vote.sign_bytes(chain_id))
        votes.append(vote)
    return chain_id, bid, height, Commit(bid, votes)


def _both(vs, plain, chain_id, bid, height, commit):
    """(the program's verdict, the reference's)."""
    from benchmark import commitref
    from benchmark.drivers.fleet_procs import plain_bid, plain_commit
    try:
        vs.verify_commit(chain_id, bid, height, commit)
        prog = True
    except ValueError:
        prog = False
    ref = commitref.verify_commit(
        chain_id, [(pk, pw) for _a, pk, pw in plain], plain_bid(bid),
        height, plain_commit(commit)) is None
    return prog, ref


def test_the_two_stake_controls_by_program_and_reference():
    from benchmark import stakeref
    vs, plain, keys = _set(100, 9)
    idx = {v.address: i for i, v in enumerate(vs.validators)}
    small = {idx[a] for a in stakeref.smallest(plain, 66)}
    large = {idx[a] for a in stakeref.largest(plain, 33)}
    assert not small & large and len(small | large) == 99
    # 66 of 100 validators, 28% of the stake: refused by both
    assert _both(vs, plain, *_commit(vs, keys, small)) == (False, False)
    assert stakeref.tally(plain, stakeref.smallest(plain, 66))[2] is False
    # 33 of 100 validators, 71% of the stake: accepted by both
    assert _both(vs, plain, *_commit(vs, keys, large)) == (True, True)
    assert stakeref.tally(plain, stakeref.largest(plain, 33))[2] is True


@pytest.mark.parametrize("seed", range(6))
def test_random_signers_around_the_two_thirds_line(seed):
    from benchmark import stakeref
    vs, plain, keys = _set(20, 100 + seed)
    rng = random.Random(seed)
    total = sum(p for _a, _k, p in plain)
    order = list(range(20))
    rng.shuffle(order)
    # grow a signer set until it crosses 2/3, and look at both sides
    signers, got = set(), 0
    for i in order:
        signers.add(i)
        got += vs.validators[i].voting_power
        if 3 * got > 2 * total:
            break
    over = _both(vs, plain, *_commit(vs, keys, signers))
    under = _both(vs, plain, *_commit(vs, keys, signers - {i}))
    assert over == (True, True) and under == (False, False)
    addrs = [vs.validators[j].address for j in signers]
    assert stakeref.tally(plain, addrs) == (got, total, True)
    assert stakeref.tally(plain, addrs + addrs)[0] == got   # once each


def test_cut_commit_keeps_only_the_named_signers():
    from benchmark.drivers.fleet_procs import cut_commit
    vs, _plain, keys = _set(7, 1)
    _c, _b, _h, commit = _commit(vs, keys, set(range(7)))
    cut = cut_commit(commit, {1, 4})
    assert [v is not None for v in cut.precommits] == [
        i in (1, 4) for i in range(7)]
    assert cut.block_id == commit.block_id


# ------------------------------------------------------ the run's clock

def test_the_deadline_fires_with_the_hung_phase_named(capfd):
    from benchmark.drivers.fleet_procs import OVERDUE_EXIT, RunClock
    killed, exits = [], []
    clock = RunClock(time.time() - 1.0, 1.6, {"boot": 8, "links": 12},
                     on_deadline=lambda: killed.append(True),
                     exit_fn=exits.append)
    with clock.phase("boot"):
        time.sleep(0.05)
    with clock.phase("links"):
        time.sleep(0.9)             # the phase that hangs
    assert killed == [True] and exits == [OVERDUE_EXIT]
    err = capfd.readouterr().err
    assert "deadline of 2 s passed in phase 'links'" in err
    doc = json.loads(err[err.index("{"):])
    assert doc["phase"] == "links" and doc["seconds"]["boot"] >= 0.05
    assert doc["seconds"]["start"] >= 1.0 and doc["seconds"]["links"] > 0.3


def test_a_wait_takes_what_is_left_of_its_phase():
    from benchmark.drivers.fleet_procs import Overdue, RunClock
    exits = []
    clock = RunClock(time.time(), 30.0, {"links": 0.1}, exit_fn=exits.append)
    with clock.phase("links"):
        assert 0.15 <= clock.left() <= 0.2      # twice the budget
        t0 = time.monotonic()
        with pytest.raises(Overdue, match="phase 'links'.*the moon"):
            clock.wait(lambda: False, "the moon")
        assert time.monotonic() - t0 < 1.0
    clock.done()
    near = RunClock(time.time(), 0.3, {"window": 45}, exit_fn=exits.append)
    with near.phase("window"):
        assert near.left() <= 0.3               # never past the deadline
    near.done()
    rep = clock.report()
    assert set(rep) == {"phase", "took_s", "seconds", "budget_s"}
    assert exits == []


def test_set_up_programs_that_were_never_called_again_are_named():
    from benchmark.drivers.fleet_procs import programs_called
    at_setup = {"pallas_full": 1, "pallas_pre": 1}
    first = {"pallas_full[2048]": 40.0, "pallas_pre[512]": 30.0}
    assert programs_called(first, at_setup, {"pallas_full": 2,
                                             "pallas_pre": 1}) == \
        ["pallas_pre[512]"]
    assert programs_called(first, at_setup, {"pallas_full": 2,
                                             "pallas_pre": 3}) == []


# ------------------------------------------- the readers, on recordings

class _R:
    def __init__(self, client, window=(100.0, 145.0)):
        self.client, self.window, self.passes = client, window, []


BLOCKS = [{"height": 10 + i, "seen_at": 4.0 * i, "txs": 240, "round": 0}
          for i in range(11)]


def _reader(name):
    import importlib
    return importlib.import_module(f"benchmark.metrics.{name}")


def test_duplicate_share_msgs_and_cpu_from_recorded_counters():
    client = {
        "blocks": BLOCKS, "in_process": 2, "workers_cpu_share": 72.5,
        "n100_open": {"votes": {"added": 100.0, "duplicate": 50.0,
                                "rejected": 0.0},
                      "msgs_recv": 1000.0, "link_delay": None},
        "n100_close": {"votes": {"added": 2080.0, "duplicate": 2470.0,
                                 "rejected": 0.0},
                       "msgs_recv": 23000.0, "link_delay": None}}
    r = _R(client)
    assert _reader("n100_votes_duplicate_share").read(r) == \
        100.0 * 2420 / 4400
    assert _reader("n100_p2p_msgs_per_height").read(r) == 22000 / 22
    assert _reader("n100_workers_cpu_share").read(r) == 72.5
    assert _reader("n100_link_delay_p50_ms").read(r) is None
    # an untraced run, or a parent without the counters: nothing
    for name in NEW:
        assert _reader(name).read(_R({"blocks": BLOCKS})) is None


def test_link_delay_median_from_two_histogram_snapshots():
    mod = _reader("n100_link_delay_p50_ms")
    inf = float("inf")
    a = {0.04: 10, 0.05: 10, 0.06: 10, 0.1: 10, 0.12: 10, inf: 10}
    # since then: 100 in (40, 50], 300 in (50, 60], 200 in (100, 120]
    b = {0.04: 10, 0.05: 110, 0.06: 410, 0.1: 410, 0.12: 610, inf: 610}
    assert mod.median_of_buckets(a, b) == pytest.approx(0.05 + 0.01 * 2 / 3)
    r = _R({"n100_open": {"link_delay": a}, "n100_close": {"link_delay": b}})
    assert mod.read(r) == pytest.approx(56.6667, abs=1e-3)
    assert mod.median_of_buckets(a, a) is None


def test_the_span_readers_read_the_programs_ring():
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        trace.TRACER.clear()
        t0 = time.perf_counter()
        for sigs, ms in ((2, 2.0), (0, 1.0), (2, 2.0), (5, 3.0), (1, 2.0)):
            a = time.perf_counter()
            trace.complete("cs:vote_ingest", a, a + ms / 1e3, req=7,
                           sigs=sigs)
        t1 = time.perf_counter() + 0.02
        r = _R({"blocks": BLOCKS[:2], "in_process": 2}, window=(t0, t1))
        assert _reader("n100_vote_batch_sigs_p50").read(r) == 2
        got = _reader("n100_vote_ingest_ms_per_height").read(r)
        # 10 ms of spans, stacked on one thread: their union, over two
        # heights and two nodes
        assert 3.0 / 4 <= got <= 10.0 / 4
    finally:
        trace.TRACER.clear()
        telemetry.set_enabled(was)


def test_vote_ingest_span_and_outcomes_come_from_the_voteset():
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.types.vote import VoteType
    vs, _plain, keys = _set(4, 2)
    chain_id, _bid, height, commit = _commit(vs, keys, {0, 1, 2})
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        trace.TRACER.clear()
        before = _reader("n100_votes_duplicate_share").now()
        t0 = time.perf_counter()
        votes = VoteSet(chain_id, height, 0, VoteType.PRECOMMIT, vs)
        assert votes.add_vote(commit.precommits[0]) is True
        assert votes.add_votes_batch(
            [commit.precommits[0], commit.precommits[1],
             commit.precommits[2]])[0] == [False, True, True]
        bad = commit.precommits[2]
        bad = type(bad)(bad.validator_address, 3, bad.height, bad.round,
                        bad.timestamp_ns, bad.type, bad.block_id,
                        bad.signature)
        with pytest.raises(ValueError):
            votes.add_vote(bad)
        after = _reader("n100_votes_duplicate_share").now()
        rows, _dropped = trace.TRACER.between("cs:vote_ingest", t0,
                                              time.perf_counter())
        assert [row["args"]["sigs"] for row in rows] == [1, 2, 0]
        assert {row["req"] for row in rows} == {height}
        assert {o: after[o] - before[o] for o in after} == {
            "added": 3.0, "duplicate": 1.0, "rejected": 1.0}
    finally:
        trace.TRACER.clear()
        telemetry.set_enabled(was)


# ----------------------------------------------------- the declaration

def test_the_cell_its_deployment_and_its_metrics_are_declared():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "net_100v"
    conf = next(c for c in M["configs"] if c["name"] == "net_100v")
    assert conf["reduced"] == ["dialled_peers"]
    with open(os.path.join(REPO, conf["file"])) as f:
        doc = json.load(f)
    assert doc["source"] == conf["source"] and doc["validators"] == 100
    assert doc["dialled_peers"] == 3 and doc["max_num_peers"] == 50
    assert doc["region_delay_ms"] == [[0, 40, 100], [40, 0, 80],
                                      [100, 80, 0]]
    assert len(doc["guarantees"]) == 6 and set(doc["assumed"]) >= {
        "stake", "regions", "region_delay_ms", "region_jitter_ms",
        "peer_graph", "tx_bytes", "stores", "one_host"}
    from tendermint_tpu.config import ConsensusConfig
    default = ConsensusConfig()
    assert doc["consensus"] == {k: getattr(default, k)
                                for k in doc["consensus"]}
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert CELL in e2e["commit_p50_ms"]["workloads"]
    for name in NEW:
        m = LAYER[name]
        assert m["workloads"] == [CELL] and m["moves"] == "commit_p50_ms"
        assert m["layer"] == "gossip and consensus rounds"
    with open(os.path.join(REPO, "benchmark", "traffic",
                           f"{CELL}.json")) as f:
        traffic = json.load(f)
    steady = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "net_4v_kvstore.writes_steady.json")))
    # the 4-validator cell's traffic but for the rate
    for key in ("keyspace", "conns", "method", "readback_sample",
                "trace_seconds", "slo_sample"):
        assert traffic["params"][key] == steady["params"][key]
    p = traffic["params"]
    assert p["rate"] % 10 == 0 and str(p["rate"]) in traffic["about"]
    assert (p["warm_blocks"], p["drain_s"], p["drain_max_s"],
            p["audit_heights"]) == (3, 20.0, 30.0, 20)
    assert sum(p["budget_s"].values()) == 200 and p["deadline_s"] == 300


def test_the_parent_of_this_cell_fails_at_once_with_keyerror():
    from benchmark.manifest import Manifest
    with pytest.raises(KeyError, match="no workload 'net_100v.nope'"):
        Manifest(REPO).cell("net_100v.nope")


# ------------------------------------------------- the whole path, small

@pytest.fixture(scope="module")
def traced():
    notes = []
    from benchmark.harness import Harness
    note = Harness.note

    def keeping(self, kind, **fields):
        notes.append({"bench": kind, **fields})
        return note(self, kind, **fields)
    Harness.note = keeping
    try:
        line = rehearse(CELL, seed=2**31 + 12, seconds=3.0, trace=True)
    finally:
        Harness.note = note
    return line, notes


@pytest.fixture(scope="module")
def traced_line(traced):
    return traced[0]


def test_rehearsal_notes_the_steps_and_timeouts_of_the_window(traced):
    """What the 4-validator cell reports as `cs_*_p50_ms` and
    `timeouts_per_100_heights` (entries a test pins to that cell) this
    cell prints as a note, from the same readers."""
    _line, notes = traced
    steps = next(n for n in notes if n["bench"] == "steps")
    assert steps["timeouts"] >= 0
    assert steps["timeouts_per_100_heights"] >= 0.0
    for key in ("newheight_p50_ms", "propose_p50_ms", "prevote_p50_ms",
                "precommit_p50_ms"):
        assert 0.0 < steps[key] < 3000.0, steps


def test_rehearsal_is_correct_and_reports_the_cells_metrics(traced_line):
    line = traced_line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 60          # 20/s for 3 s, to the write
    got = set(line["metrics"])
    assert set(NEW) <= got
    # every per-layer metric that lists the cell finds something to
    # read here, but for the one the profiler's trace of a chip feeds
    listed = {m["name"] for m in M["per_layer"]
              if CELL in m.get("workloads", ())}
    assert len(listed) == 12 + len(NEW)
    assert listed - {"net_device_idle_share"} <= got, listed - got
    v = {k: m["value"] for k, m in line["metrics"].items()}
    assert v["n100_vote_batch_sigs_p50"] >= 1
    assert 0 < v["n100_votes_duplicate_share"] < 100
    # a tenth of wan3: 4 to 12 ms held
    assert 4.0 <= v["n100_link_delay_p50_ms"] <= 12.0
    assert v["n100_workers_cpu_share"] > 0
    assert v["n100_p2p_msgs_per_height"] > 10
    assert v["steady_committed_tx_per_s"] == 20.0


def test_rehearsal_untraced_line_and_no_process_left(capfd):
    line = rehearse(CELL, seed=2**31 + 13, seconds=2.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"commit_p50_ms", "setup_s"}
    out = capfd.readouterr().out
    notes = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    clock = next(n for n in notes if n.get("bench") == "clock")
    assert clock["phase"] == "stop" and set(clock["seconds"]) == {
        "start", "compile", "boot", "links", "warm", "window", "drain",
        "settle", "checks", "stop"}
    checks = {n["check"] for n in notes if n.get("bench") == "check"}
    assert checks == {
        "app_hashes_differing_from_plain_reference",
        "window_writes_never_committed",
        "acknowledged_writes_missing_from_log",
        "read_backs_differing_from_plain_reference",
        "nodes_disagreeing_at_last_height", "peers_banned_in_run",
        "declared_links_not_held", "commits_short_of_two_thirds_stake",
        "proposers_differing_from_plain_reference",
        "stake_controls_answered_wrongly",
        "audit_forged_header_not_rejected_at_its_height"}
    net = next(n for n in notes if n.get("bench") == "net")
    assert net["validators"] == 7 and net["workers"] == 2
    assert sorted(net["in_process_ranks"]) == [3, 4]
    import subprocess
    time.sleep(0.5)
    left = subprocess.run(["pgrep", "-f", "bench-net100"],
                          capture_output=True, text=True).stdout.split()
    assert left == []
