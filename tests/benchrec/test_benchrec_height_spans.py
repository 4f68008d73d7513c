"""The readers of what PR 37 put on the program's recorder
(benchmark/metrics/cs_commit*_p50_ms, cs_propose_*_p50_ms,
cs_await_*_p50_ms, nil_prevotes_*, queue_saturations_in_window,
net_gc_pause_share, sync_gc_pause_share, rpc_write_*_ms): their
entries, the arithmetic on a ring and on histograms filled by hand, what
they do where the program has
no such name (a parent commit), and the traced rehearsals of the two
cells that list them."""

import time
from types import SimpleNamespace

import pytest

from benchmark.manifest import Manifest
from benchmark.passes import Pass
from benchrec_util import REPO, manifest, rehearse

STEADY = "net_4v_kvstore.writes_steady"
SYNC = "chain_64v.fastsync_5ktx"
G = "gossip and consensus rounds"

# name: (cells, layer, moves, source, the span it reads)
SPAN_P50 = {
    "cs_commit_p50_ms": "cs:COMMIT",
    "cs_commit_validate_p50_ms": "cs:commit.validate",
    "cs_commit_persist_p50_ms": "cs:commit.persist",
    "cs_propose_build_p50_ms": "cs:propose.build",
    "cs_propose_send_p50_ms": "cs:propose.send",
    "cs_await_proposal_p50_ms": "cs:propose.await_proposal",
    "cs_await_block_p50_ms": "cs:propose.await_block",
}
DECLARED = dict(
    {name: ([STEADY], G, "commit_p50_ms", "program_span")
     for name in SPAN_P50},
    nil_prevotes_per_100_heights=([STEADY], G, "commit_p50_ms",
                                  "program_span"),
    nil_prevotes_no_proposal_share=([STEADY], G, "commit_p50_ms",
                                    "program_span"),
    queue_saturations_in_window=([STEADY], G, "commit_p50_ms",
                                 "program_span"),
    net_gc_pause_share=([STEADY], "host runtime", "commit_p50_ms",
                        "program_span"),
    sync_gc_pause_share=([SYNC], "host runtime", "commits_per_s",
                         "program_span"),
    rpc_write_queue_ms=([STEADY], "front door", "commit_p50_ms",
                        "program_counter"),
    rpc_write_reply_ms=([STEADY], "front door", "commit_p50_ms",
                        "program_counter"),
)


def reader(name):
    return Manifest(REPO).reader(name)


def reading(base, blocks=(), passes=()):
    return SimpleNamespace(
        window=(base, base + 10.0), setup_s=30.0,
        passes=[Pass(base + a, d, 1) for a, d in passes],
        client={"blocks": list(blocks)}, counters={})


@pytest.fixture
def ring(monkeypatch):
    """A tracer of its own in the program's place, telemetry on."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    t = trace.Tracer(capacity=256)
    monkeypatch.setattr(trace, "TRACER", t)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    yield t
    telemetry.set_enabled(was)


# ------------------------------------------------------------ the manifest

@pytest.mark.parametrize("name", sorted(DECLARED))
def test_each_entry_is_declared_as_its_reader_says(name):
    cells, layer, moves, source = DECLARED[name]
    doc = manifest()
    (m,) = [m for m in doc["per_layer"] if m["name"] == name]
    assert (m["workloads"], m["layer"], m["moves"], m["source"],
            m["better"]) == (cells, layer, moves, source, "lower")
    e2e = {e["name"]: e for e in doc["end_to_end"]}
    assert all(cell in e2e[moves]["workloads"] for cell in cells)
    assert (reader(name).LAYER, reader(name).MOVES) == (layer, moves)


def test_the_entries_follow_everything_that_was_there():
    names = [m["name"] for m in manifest()["per_layer"]]
    first = min(names.index(n) for n in DECLARED)
    assert set(names[first:first + len(DECLARED)]) == set(DECLARED)
    assert names[first - 1] == "ycsb_state_MB_per_validator"    # PR 35's last


def test_every_span_these_readers_name_is_in_the_programs_catalogue():
    import os
    import re
    from tendermint_tpu.telemetry.trace import SPANS
    named = set()
    for name in DECLARED:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               name + ".py")) as f:
            body = f.read().split('"""', 2)[2]
        named |= set(re.findall(r'"((?:cs:|queue\.|gc\.)[A-Za-z_.]+)"', body))
    assert named == set(SPAN_P50.values()) | {
        "cs:nil_vote", "queue.saturated", "gc.collect"}
    assert named <= set(SPANS)


# ------------------------------------------- the arithmetic, ring by hand

@pytest.mark.parametrize("name", sorted(SPAN_P50))
def test_a_part_of_a_step_is_a_median_over_node_and_height(ring, name):
    span = SPAN_P50[name]
    base = time.perf_counter()
    r = reading(base)
    for node, height, ms in (("a", 5, 10.0), ("b", 5, 30.0), ("a", 6, 20.0)):
        ring.complete(span, base + 1.0, base + 1.0 + ms / 1e3, req=height,
                      round=0, node=node)
    # a second round of one height adds to that node's stay
    ring.complete(span, base + 2.0, base + 2.05, req=5, round=1, node="b")
    # ended after the window closed, began before it opened: left out
    ring.complete(span, base + 9.9, base + 10.4, req=7, round=0, node="a")
    ring.complete(span, base - 0.2, base + 0.1, req=4, round=0, node="a")
    # (a,5) 10, (a,6) 20, (b,5) 80: the nearest-rank median is 20
    assert reader(name).read(r) == pytest.approx(20.0)


def test_a_wait_of_no_seconds_counts_as_one(ring):
    base = time.perf_counter()
    r = reading(base)
    for node in "abc":
        ring.complete("cs:propose.await_proposal", base + 1.0, base + 1.0,
                      req=5, round=0, node=node)
    assert reader("cs_await_proposal_p50_ms").read(r) == 0.0
    # never ran in the window: nothing to take a median of
    assert reader("cs_await_block_p50_ms").read(r) is None


def test_nil_prevotes_are_counted_per_100_heights_and_by_why(ring):
    base = time.perf_counter()
    r = reading(base, blocks=[{"height": h} for h in range(5, 25)])
    per_100, share = (reader("nil_prevotes_per_100_heights"),
                      reader("nil_prevotes_no_proposal_share"))
    # no lost round is no round lost for want of a proposal: 0, not absent
    assert per_100.read(r) == 0.0 and share.read(r) == 0.0
    for node, why in (("a", "no_proposal"), ("b", "no_proposal"),
                      ("c", "no_block")):                       # now
        ring.instant("cs:nil_vote", req=9, type="prevote", round=0, node=node,
                     why=why)
    ring.instant("cs:nil_vote", req=9, type="precommit", round=0, node="a",
                 why="polka_nil")
    assert per_100.read(r) == pytest.approx(100.0 * 3 / 20)
    assert share.read(r) == pytest.approx(100.0 * 2 / 3)
    r.window = (base - 20.0, base - 10.0)       # a window before them
    assert per_100.read(r) == 0.0 and share.read(r) == 0.0
    r.client["blocks"] = []
    assert per_100.read(r) is None


def test_queue_saturations_are_the_instants_that_began_in_the_window(ring):
    base = time.perf_counter()
    r = reading(base)
    q = reader("queue_saturations_in_window")
    assert q.read(r) == 0
    ring.instant("queue.saturated", queue="mconn.send.0x22", depth=90)
    ring.instant("queue.saturated", queue="mconn.send.0x20", depth=85)
    assert q.read(r) == 2
    r.window = (base - 20.0, base - 10.0)
    assert q.read(r) == 0


def test_the_syncing_threads_pauses_are_clipped_to_the_passes(ring):
    base = time.perf_counter()
    r = reading(base, passes=((1.0, 2.0), (5.0, 2.0)))
    ring.complete("gc.collect", base + 1.0, base + 1.1, gen=2, collected=0)
    ring.complete("gc.collect", base + 2.9, base + 3.5, gen=1, collected=9)
    ring.complete("gc.collect", base + 3.6, base + 4.4, gen=2, collected=0)
    # 0.1 + 0.1 inside the passes' 4 s; the third fell between them
    assert reader("sync_gc_pause_share").read(r) == pytest.approx(5.0)
    assert reader("sync_gc_pause_share").read(reading(base)) is None


def test_the_nets_pauses_are_clipped_to_the_window(ring):
    base = time.perf_counter()
    r = reading(base)
    assert reader("net_gc_pause_share").read(r) == 0.0
    # set-up's, the harness's own before the window: outside
    ring.complete("gc.collect", base - 3.0, base - 2.8, gen=2, collected=0)
    ring.complete("gc.collect", base - 0.1, base + 0.1, gen=2, collected=0)
    ring.complete("gc.collect", base + 4.0, base + 4.3, gen=1, collected=7)
    ring.complete("gc.collect", base + 9.9, base + 10.5, gen=0, collected=0)
    # 0.1 + 0.3 + 0.1 of the window's 10 s
    assert reader("net_gc_pause_share").read(r) == pytest.approx(5.0)


# ----------------------------------------------------------- histograms

@pytest.mark.parametrize("name, family, route", [
    ("rpc_write_queue_ms", "rpc_queue_seconds", "broadcast_tx_sync"),
    ("rpc_write_reply_ms", "rpc_reply_seconds", "broadcast_tx_sync"),
])
def test_a_wait_at_the_front_door_is_its_histograms_mean(name, family, route):
    from tendermint_tpu import telemetry
    from tendermint_tpu.rpc import aserver     # declares the families
    fam = telemetry.REGISTRY.get(family)
    assert fam in (aserver._m_queue_seconds, aserver._m_reply_seconds)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    child = fam.labels(route)
    counts, total, n = child.snapshot()
    try:
        with child._lock:
            child.counts, child.sum, child.count = [0] * len(counts), 0.0, 0
        assert reader(name).read(None) is None      # no call yet
        for seconds in (0.002, 0.004, 0.012):
            child.observe(seconds)
        assert reader(name).read(None) == pytest.approx(6.0)
    finally:
        with child._lock:
            child.counts, child.sum, child.count = list(counts), total, n
        telemetry.set_enabled(was)


# ------------------------------------------------------- a parent commit

def test_a_program_without_these_names_reads_nothing(monkeypatch, ring):
    """What the parent commit looks like to the readers: no such span
    in its catalogue, no such family in its registry. Nothing raises."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    base = time.perf_counter()
    r = reading(base, blocks=[{"height": 1}], passes=((1.0, 2.0),))
    parents = {k: v for k, v in trace.SPANS.items()
               if k == "cs:COMMIT" or not k.startswith(
                   ("cs:propose.", "cs:commit.", "cs:nil", "queue.", "gc."))}
    monkeypatch.setattr(trace, "SPANS", parents)
    get = telemetry.REGISTRY.get
    monkeypatch.setattr(
        telemetry.REGISTRY, "get",
        lambda name: None if name.startswith(("gc_", "rpc_queue", "rpc_reply"))
        else get(name))
    got = {name: reader(name).read(r) for name in DECLARED}
    # the COMMIT step's span was there before its reader
    assert {n for n, v in got.items() if v is not None} <= {
        "cs_commit_p50_ms"}
    monkeypatch.delattr(trace, "SPANS")
    assert all(reader(n).read(r) is None for n in DECLARED
               if DECLARED[n][3] == "program_span")


# ------------------------------------------------ the traced rehearsals

def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_steady_traced_rehearsal_takes_propose_and_commit_apart():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(STEADY, trace=True)
    assert line["correct"] is True
    got = values(line)
    listed = {n for n, d in DECLARED.items() if STEADY in d[0]}
    # every listed metric is in every traced line, lost round or none
    assert listed <= set(got)
    assert 0.0 <= got["nil_prevotes_no_proposal_share"] <= 100.0
    assert all(got[n] >= 0.0 for n in listed & set(got))
    # parts of a step are no longer than the step
    assert got["cs_commit_validate_p50_ms"] + \
        got["cs_commit_persist_p50_ms"] <= got["cs_commit_p50_ms"] * 1.5
    assert got["cs_await_proposal_p50_ms"] <= got["cs_propose_p50_ms"] * 1.5
    assert got["cs_commit_p50_ms"] > 0 and got["cs_propose_build_p50_ms"] > 0
    assert got["nil_prevotes_per_100_heights"] <= 100.0 * 4
    assert got["queue_saturations_in_window"] >= 0
    assert 0.0 <= got["net_gc_pause_share"] < 100.0
    assert got["rpc_write_queue_ms"] > 0 and got["rpc_write_reply_ms"] > 0
    # and the untraced line is the end-to-end metrics alone
    assert set(values(rehearse(STEADY))) == {"commit_p50_ms", "setup_s"}


def test_sync_traced_rehearsal_reports_the_collectors_share():
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    line = rehearse(SYNC, trace=True)
    assert line["correct"] is True
    assert 0.0 <= values(line)["sync_gc_pause_share"] < 100.0
