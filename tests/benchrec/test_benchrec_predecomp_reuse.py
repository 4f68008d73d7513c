"""`lite_predecomp_reuse_share` (PR 25): the share of the chunks that
got predecompressed rows whose arrays came from the memo of whole key
sequences, read from `verifier_predecomp_assembled_total{how}`. What
the reader does on a program without the family (the parent commit),
its arithmetic on a registry filled by hand and by the program's own
counting, and the lite rehearsal, which verifies on the host and so
leaves the metric out, as it leaves `lite_h2d_bytes_per_sig` out."""

import pytest

from benchmark import program_spans
from benchmark.manifest import Manifest
from benchmark.metrics import lite_predecomp_reuse_share as reader
from benchrec_util import REPO, manifest, rehearse

LITE = "chain_64v.lite_certify"
NAME = "lite_predecomp_reuse_share"
FAMILY = "verifier_predecomp_assembled_total"


@pytest.fixture
def family():
    """The program's own family, telemetry on, counting from zero."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.models import verifier     # declares the family
    fam = telemetry.REGISTRY.get(FAMILY)
    assert fam is verifier._m_predecomp_assembled
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    for how in ("reused", "built"):
        fam.labels(how).value = 0.0
    yield fam
    telemetry.set_enabled(was)


def test_the_entry_is_appended_for_the_lite_cell_alone():
    doc = manifest()
    m = doc["per_layer"][-1]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "verifier",
                 "moves": "headers_per_s", "workloads": [LITE]}
    assert Manifest(REPO).reader(NAME) is reader
    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
    assert NAME in [x["name"] for x in Manifest(REPO).metrics(
        LITE, "per_layer")]


def test_a_program_without_the_family_reads_nothing(monkeypatch, family):
    from tendermint_tpu import telemetry
    family.labels("reused").inc(5)
    assert reader.read(None) == pytest.approx(100.0)
    # the parent commit: the registry has no such family
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    assert program_spans.counter_total(FAMILY) is None
    assert reader.read(None) is None
    monkeypatch.undo()
    # and what test_a_program_without_the_span_reads_nothing does to
    # the readers of PR 24
    monkeypatch.setattr(program_spans, "counter_total", lambda *a: None)
    assert reader.read(None) is None


def test_the_share_is_reused_over_reused_and_built(family):
    assert reader.read(None) is None    # no chunk got rows: nothing to divide
    family.labels("built").inc(7)
    assert reader.read(None) == 0.0
    family.labels("reused").inc(313)
    assert reader.read(None) == pytest.approx(100.0 * 313 / 320)


def test_the_share_follows_the_programs_own_counting(family):
    """full, fill, hit, then two reuses of one key sequence, through
    verify_batch at a shape the CPU compiles in seconds."""
    from bench_util import fast_signer
    from tendermint_tpu.ops import ed25519
    from tendermint_tpu.utils import ed25519_ref as ref
    pubs, msgs, sigs = [], [], []
    for i in range(8):
        seed = bytes([25, i]) * 16
        msgs.append(b"reuse share %d" % i)
        pubs.append(ref.public_key(seed))
        sigs.append(fast_signer(seed)(msgs[-1]))
    caches = (ed25519._predecomp, ed25519._predecomp_seen,
              ed25519._predecomp_memo)
    gate = ed25519._PREDECOMP_MIN_BATCH
    ed25519._PREDECOMP_MIN_BATCH = 8
    for c in caches:
        c.clear()
    try:
        want = []
        for outcome in ("full", "fill", "hit", "hit", "hit"):
            before = ed25519.predecomp_stats()[outcome]
            assert ed25519.verify_batch(pubs, msgs, sigs).all()
            assert ed25519.predecomp_stats()[outcome] == before + 1
            want.append(reader.read(None))
    finally:
        ed25519._PREDECOMP_MIN_BATCH = gate
        for c in caches:
            c.clear()
    # `full` gets no rows and counts neither; fill and the first hit build
    assert want == [None, 0.0, 0.0, pytest.approx(100.0 / 3), 50.0]


def test_the_lite_rehearsal_leaves_the_metric_out(family):
    # (the counter has no window: `family` takes earlier tests' counts out)
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # host-verified batches: no chunk got rows, no bytes went to the device
    assert NAME not in got and "lite_h2d_bytes_per_sig" not in got
    assert "lite_predecomp_share" in got and "lite_collect_share" in got
