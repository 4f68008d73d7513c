"""The per-layer metrics that read the program's own span recorder
(benchmark/program_spans.py and its readers under benchmark/metrics/):
the arithmetic on a ring filled by hand, what a reader does where the
program has no such span or the ring lost one, and each cell's traced
rehearsal with the coherence checks that need no chip. The rehearsals
verify on the host (every batch sits under auto_threshold), so the
device-path spans read 0 there; tests/test_telemetry.py drives those
through a real dispatch."""

import threading
import time
from types import SimpleNamespace

import pytest

from benchmark import program_spans
from benchmark.manifest import Manifest
from benchmark.passes import Pass
from benchrec_util import REPO, manifest, rehearse

LITE = "chain_64v.lite_certify"
SYNC = "chain_64v.fastsync_5ktx"
STEADY = "net_4v_kvstore.writes_steady"

NEW = {
    LITE: {"lite_collect_share", "lite_prep_share", "lite_predecomp_share",
           "lite_enqueue_share", "lite_check_share", "lite_wait_share",
           "lite_fetch_share", "lite_starved_share",
           "lite_h2d_bytes_per_sig"},
    SYNC: {"sync_parts_share", "sync_store_share", "sync_wait_share",
           "program_decode_share", "apply_validate_share",
           "apply_exec_share", "apply_commit_share", "apply_save_share"},
    STEADY: {"cs_newheight_p50_ms", "cs_propose_p50_ms",
             "cs_prevote_p50_ms", "cs_precommit_p50_ms",
             "timeouts_per_100_heights"},
}


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


line_notes = []


@pytest.fixture(autouse=True)
def _fresh_ring_and_notes(monkeypatch):
    """Each test reads a ring that holds its own run alone, and keeps
    the harness's notes of that run for the arithmetic."""
    from benchmark.harness import Harness
    from tendermint_tpu import telemetry
    telemetry.TRACER.clear()
    del line_notes[:]
    note = Harness.note

    def keeping(self, kind, **fields):
        line_notes.append({"bench": kind, **fields})
        return note(self, kind, **fields)
    monkeypatch.setattr(Harness, "note", keeping)


# ------------------------------------------------------------ the manifest

@pytest.mark.parametrize("cell", sorted(NEW))
def test_new_metrics_are_declared_for_their_cell_alone(cell):
    doc = manifest()
    by = {m["name"]: m for m in doc["per_layer"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for name in NEW[cell]:
        m = by[name]
        assert m["workloads"] == [cell]
        assert m["source"] in ("program_span", "program_counter")
        assert cell in e2e[m["moves"]]["workloads"]
        reader = Manifest(REPO).reader(name)
        assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])


def test_every_span_a_reader_names_is_in_the_programs_catalogue():
    import os
    import re
    from tendermint_tpu.telemetry.trace import SPANS
    named = set()
    for name in set().union(*NEW.values()):
        with open(os.path.join(REPO, "benchmark", "metrics",
                               name + ".py")) as f:
            body = f.read().split('"""', 2)[2]
        named |= set(re.findall(r'"((?:verify|lite|sync|wire|apply|cs)'
                                r'[.:][A-Za-z_.]+)"', body))
    assert len(named) >= 20 and named <= set(SPANS)


# ------------------------------------------- the arithmetic, ring by hand

@pytest.fixture
def ring(monkeypatch):
    """A tracer of its own in the program's place, telemetry on."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.telemetry import trace
    t = trace.Tracer(capacity=64)
    monkeypatch.setattr(trace, "TRACER", t)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    yield t
    telemetry.set_enabled(was)


def on_thread(fn):
    th = threading.Thread(target=fn)
    th.start()
    th.join()


def reading(base, passes=((1.0, 2.0), (5.0, 2.0)), blocks=()):
    return SimpleNamespace(
        window=(base, base + 10.0),
        passes=[Pass(base + a, d, 1) for a, d in passes],
        client={"blocks": list(blocks)}, counters={})


def test_shares_clip_to_the_passes_and_union_per_thread(ring):
    base = time.perf_counter()
    r = reading(base)
    # one thread: a repeat and a nested span count once; what lies
    # between the passes or before the window does not count
    ring.complete("lite.collect", base + 1.0, base + 1.5)
    ring.complete("lite.collect", base + 1.2, base + 1.4)
    ring.complete("lite.collect", base + 2.9, base + 3.6)   # 0.1 inside
    ring.complete("lite.collect", base - 3.0, base + 0.5)   # none inside
    ring.complete("lite.collect", base + 6.0, base + 6.4)
    assert program_spans.share_of_passes(r, "lite.collect") == \
        pytest.approx(100.0 * (0.5 + 0.1 + 0.4) / 4.0)
    # two threads at once count as two: 2 x 0.5 s (both alive, so the
    # interpreter cannot hand the second the first one's ident)
    both = threading.Barrier(2)

    def fetch():
        ring.complete("verify.fetch", base + 1.0, base + 1.5)
        both.wait(timeout=10.0)
    threads = [threading.Thread(target=fetch) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert program_spans.share_of_passes(r, "verify.fetch") == \
        pytest.approx(25.0)
    # a span the catalogue has, that never ran: 0, a measurement
    assert program_spans.share_of_passes(r, "verify.prep") == 0.0


def test_starved_share_is_what_no_inflight_interval_covers(ring):
    base = time.perf_counter()
    r = reading(base)
    on_thread(lambda: ring.complete("verify.inflight", base + 1.4,
                                    base + 2.6))
    on_thread(lambda: ring.complete("verify.inflight", base + 2.0,
                                    base + 3.5))   # overlaps, runs past
    # covered: 1.4 .. 3.0 of the first pass, nothing of the second
    assert program_spans.uncovered_share_of_passes(
        r, "verify.inflight") == pytest.approx(100.0 - 100.0 * 1.6 / 4.0)
    from benchmark.metrics import lite_starved_share
    assert lite_starved_share.read(r) == pytest.approx(60.0)


def test_consensus_steps_are_medians_over_node_and_height(ring):
    base = time.perf_counter()
    r = reading(base, passes=(), blocks=[{"height": h} for h in (5, 6, 7, 8)])
    for node, ms in (("a", 10.0), ("b", 30.0)):
        ring.complete("cs:PREVOTE", base + 1.0, base + 1.0 + ms / 1e3,
                      req=5, node=node)
    # a second round: the height's two stays add up, for that node
    ring.complete("cs:PREVOTE_WAIT", base + 2.0, base + 2.05, req=5,
                  node="b")
    ring.complete("cs:PREVOTE", base + 3.0, base + 3.02, req=6, node="a")
    # ended after the window closed, began before it opened: left out
    ring.complete("cs:PREVOTE", base + 9.9, base + 10.4, req=7, node="a")
    ring.complete("cs:PREVOTE", base - 0.2, base + 0.1, req=4, node="a")
    from benchmark.metrics import cs_prevote_p50_ms, timeouts_per_100_heights
    # (a,5) 10, (a,6) 20, (b,5) 80: the nearest-rank median is 20
    assert cs_prevote_p50_ms.read(r) == pytest.approx(20.0)
    assert timeouts_per_100_heights.read(r) == 0.0
    ring.instant("cs:timeout", req=6, step="PROPOSE", node="a")     # now
    assert timeouts_per_100_heights.read(r) == pytest.approx(25.0)
    r.window = (base - 20.0, base - 10.0)
    assert timeouts_per_100_heights.read(r) == 0.0


def test_a_ring_that_lost_a_window_event_reads_nothing(ring):
    base = time.perf_counter()
    r = reading(base)
    ring.complete("sync.parts", base - 5.0, base - 4.0)     # before it
    for i in range(64):
        ring.complete("sync.parts", base + 1.0, base + 1.1)
    # the one displaced event ended before the window: still sound
    assert ring.dropped == 1
    assert program_spans.share_of_passes(r, "sync.parts") == \
        pytest.approx(2.5)
    ring.complete("sync.store", base + 1.0, base + 1.1)     # displaces one
    assert ring.dropped == 2
    from benchmark.metrics import sync_parts_share, sync_store_share
    assert sync_parts_share.read(r) is None
    assert sync_store_share.read(r) is None


def test_a_program_without_the_span_reads_nothing(monkeypatch, ring):
    """What the parent commit looks like to the readers: no catalogue,
    or a catalogue without the name. Nothing raises."""
    from tendermint_tpu.telemetry import trace
    base = time.perf_counter()
    r = reading(base, blocks=[{"height": 1}])
    readers = [Manifest(REPO).reader(n) for n in set().union(*NEW.values())]
    catalogue = dict(trace.SPANS)
    monkeypatch.setattr(trace, "SPANS", {})
    monkeypatch.setattr(program_spans, "counter_total", lambda *a: None)
    assert [m.read(r) for m in readers] == [None] * len(readers)
    monkeypatch.delattr(trace, "SPANS")
    assert [m.read(r) for m in readers] == [None] * len(readers)
    monkeypatch.setattr(trace, "SPANS", catalogue, raising=False)
    monkeypatch.delattr(trace.Tracer, "between")
    assert [m.read(r) for m in readers] == [None] * len(readers)
    assert program_spans.counter_total("verifier_no_such_total") is None


def test_h2d_bytes_per_sig_is_the_counter_over_the_device_sigs(monkeypatch):
    from benchmark.metrics import lite_h2d_bytes_per_sig
    from tendermint_tpu.models import verifier
    fake = verifier.BatchVerifier("python")
    monkeypatch.setattr(verifier, "_default", fake)
    monkeypatch.setattr(program_spans, "counter_total",
                        lambda name: {"verifier_h2d_bytes_total": 1288.0}[name])
    assert lite_h2d_bytes_per_sig.read(None) is None    # nothing went there
    fake.stats["jax_sigs"] = 8
    assert lite_h2d_bytes_per_sig.read(None) == pytest.approx(161.0)


# ------------------------------------------------ the traced rehearsals

def test_lite_traced_rehearsal_reports_the_pass_by_leg():
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True
    got = values(line)
    # nothing went to the device here, so there is no byte count to
    # divide; every other new metric is in the line
    assert NEW[LITE] - set(got) == {"lite_h2d_bytes_per_sig"}
    shares = {k: got[k] for k in NEW[LITE] & set(got)}
    assert all(0.0 <= v <= 100.0 for v in shares.values()), shares
    # the certifier's own thread: its legs are disjoint
    main = sum(got[k] for k in (
        "lite_collect_share", "lite_prep_share", "lite_predecomp_share",
        "lite_enqueue_share", "lite_check_share", "lite_wait_share"))
    assert 0.0 < main <= 100.5
    assert got["lite_collect_share"] > 0 and got["lite_check_share"] > 0
    # host-verified batches: nothing was enqueued, fetched or in flight
    assert got["lite_enqueue_share"] == got["lite_fetch_share"] == 0.0
    assert got["lite_starved_share"] == 100.0


def test_sync_traced_rehearsal_splits_collect_decode_and_apply():
    line = rehearse(SYNC, trace=True)
    assert line["correct"] is True
    got = values(line)
    assert NEW[SYNC] <= set(got)
    assert all(0.0 < got[k] <= 100.0 for k in NEW[SYNC]), got
    # building part sets is a part of collecting a window
    assert got["sync_parts_share"] <= got["window_collect_share"]
    # the same call timed from inside and from outside
    assert got["program_decode_share"] <= got["wire_decode_share"]
    assert got["program_decode_share"] >= 0.6 * got["wire_decode_share"]
    # the legs of apply_block and the store lie inside _apply_window
    legs = sum(got[k] for k in ("apply_validate_share", "apply_exec_share",
                                "apply_commit_share", "apply_save_share",
                                "sync_store_share"))
    passes_s = [n for n in line_notes if n["bench"] == "passes"][-1]
    blocks = line["attempted"] - line["failed"]
    legs_ms = legs / 100.0 * sum(passes_s["seconds"]) * 1000.0 / blocks
    assert 0.5 * got["apply_ms_per_block"] <= legs_ms <= \
        got["apply_ms_per_block"]
    # every span of one window shares its request id, and the blocks'
    # own spans carry their height
    from tendermint_tpu import telemetry
    evs = telemetry.TRACER.events()
    by_id = {e["id"]: e for e in evs if "id" in e}
    parts = [e for e in evs if e["name"] == "sync.parts"]
    assert parts and all(
        by_id[e["parent"]]["name"] == "sync.collect" and
        by_id[e["parent"]]["req"] == e["req"] for e in parts)
    stores = [e for e in evs if e["name"] == "sync.store"]
    assert stores and all(
        by_id[e["parent"]]["name"] == "sync.apply" and
        by_id[e["parent"]]["req"] == e["req"] for e in stores)
    execs = [e for e in evs if e["name"] == "apply.exec"]
    assert execs and all(by_id[e["parent"]]["name"] == "sync.apply" and
                         e["req"] >= by_id[e["parent"]]["req"] for e in execs)


def test_net_traced_rehearsal_reports_the_steps_per_height():
    line = rehearse(STEADY, seed=2**31 + 5, seconds=2.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    got = values(line)
    assert NEW[STEADY] <= set(got)
    steps = [got[k] for k in NEW[STEADY] if k.endswith("_p50_ms")]
    assert all(0.0 < v < 2000.0 for v in steps), got
    assert got["timeouts_per_100_heights"] >= 0.0
    # a height's steps fit in the time between blocks, give or take the
    # spread between nodes
    assert sum(steps) <= 3.0 * got["block_interval_ms"]
    # every step names its node and its height; four nodes are there
    from tendermint_tpu import telemetry
    evs = [e for e in telemetry.TRACER.events()
           if e["name"].startswith("cs:")]
    assert len({e["args"]["node"] for e in evs}) == 4
    assert all(e["args"]["node"] and e["req"] >= 1 for e in evs)
    assert all(e["req"] == e["args"]["height"] for e in evs
               if "height" in e["args"])
    # a timeout that moved a state names which one it was
    assert all(e["args"]["step"] in ("PROPOSE", "PREVOTE_WAIT",
                                     "PRECOMMIT_WAIT")
               for e in evs if e["name"] == "cs:timeout")
