"""`decode_native_share`, the per-layer metric PR 33 appended to
BENCHMARK.json: its entry, by name; what its reader makes of counted
values and of a program that has no such family (the parent commit);
and the traced rehearsal of the cell that lists it."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchrec"))

from benchmark import program_spans                        # noqa: E402
from benchmark.manifest import Manifest                    # noqa: E402
from benchmark.metrics import decode_native_share as reader  # noqa: E402
from benchrec_util import REPO, manifest, rehearse         # noqa: E402

NAME = "decode_native_share"
SYNC = "chain_64v.fastsync_5ktx"
FAMILY = "wire_block_decodes_total"


def test_the_entry_and_its_reader():
    m, = [x for x in manifest()["per_layer"] if x["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "sync window engine",
                 "moves": "commits_per_s", "workloads": [SYNC]}
    man = Manifest(REPO)
    assert man.reader(NAME) is reader
    assert (reader.LAYER, reader.MOVES, reader.FAMILY) == (
        m["layer"], m["moves"], FAMILY)
    for cell in (w["name"] for w in manifest()["workloads"]):
        listed = NAME in [x["name"] for x in man.metrics(cell, "per_layer")]
        assert listed == (cell == SYNC)
    assert "commits_per_s" in [x["name"]
                               for x in man.metrics(SYNC, "end_to_end")]


@pytest.mark.parametrize("native, pure, want", [
    (0, 0, None), (0, 512, 0.0), (512, 512, 50.0), (2600, 0, 100.0),
    (2599, 1, 100.0 * 2599 / 2600)])
def test_the_share_is_native_over_native_and_pure(block_decodes, native,
                                                  pure, want):
    block_decodes.labels("native").inc(native)
    block_decodes.labels("pure").inc(pure)
    got = reader.read(None)
    assert got is None if want is None else got == pytest.approx(want)


def test_a_program_without_the_family_reads_nothing(monkeypatch,
                                                    block_decodes):
    from tendermint_tpu import telemetry
    block_decodes.labels("native").inc(3)
    block_decodes.labels("pure").inc(1)
    assert reader.read(None) == pytest.approx(75.0)
    # the parent commit: the registry has no such family
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    assert program_spans.counter_total(FAMILY) is None
    assert reader.read(None) is None


def test_the_rehearsal_decodes_every_block_natively(block_decodes):
    from tendermint_tpu import native
    if native.codec() is None:
        pytest.skip("native codec unavailable")
    line = rehearse(SYNC, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME]["value"] == 100.0
    assert block_decodes.labels("native").value > 0
    assert block_decodes.labels("pure").value == 0
    # the spans that time the same call still read something
    assert line["metrics"]["program_decode_share"]["value"] > 0
    assert line["metrics"]["wire_decode_share"]["value"] > 0


def test_the_line_of_a_program_without_the_counter(monkeypatch, block_decodes):
    """The parent commit with these files laid over it: a line, with
    this metric left out and the rest as they were."""
    from tendermint_tpu import telemetry
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    line = rehearse(SYNC, trace=True)
    assert line["correct"] is True
    assert NAME not in line["metrics"]
    assert line["metrics"]["program_decode_share"]["value"] > 0
