"""`lite_shared_block_id_share`, the per-layer metric PR 29 appended to
BENCHMARK.json: its entry, by name; what its reader makes of counted
values, of a program that has no such family (the parent commit) and of
one that sent nothing to a device (a rehearsal); and the traced
rehearsal of the cell that lists it."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchrec"))

from benchmark import program_spans                        # noqa: E402
from benchmark.manifest import Manifest                    # noqa: E402
from benchmark.metrics import (                            # noqa: E402
    lite_shared_block_id_share as reader)
from benchrec_util import REPO, manifest, rehearse         # noqa: E402

NAME = "lite_shared_block_id_share"
LITE = "chain_64v.lite_certify"
FAMILY = "verifier_commit_block_ids_total"


def test_the_entry_and_its_reader():
    m, = [x for x in manifest()["per_layer"] if x["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "verifier",
                 "moves": "headers_per_s", "workloads": [LITE]}
    man = Manifest(REPO)
    assert man.reader(NAME) is reader
    assert (reader.LAYER, reader.MOVES, reader.FAMILY) == (
        m["layer"], m["moves"], FAMILY)
    assert NAME in [x["name"] for x in man.metrics(LITE, "per_layer")]
    assert "headers_per_s" in [x["name"]
                               for x in man.metrics(LITE, "end_to_end")]


@pytest.fixture
def family():
    """The program's own family, telemetry on, counting from zero."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.types import block         # declares the family
    fam = telemetry.REGISTRY.get(FAMILY)
    assert fam is block._m_block_ids
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    held = {how: fam.labels(how).value for how in ("built", "shared")}
    for how in held:
        fam.labels(how).value = 0.0
    yield fam
    for how, value in held.items():
        fam.labels(how).value = value
    telemetry.set_enabled(was)


@pytest.fixture
def sent_to_a_device(monkeypatch):
    from tendermint_tpu.models import verifier
    fake = verifier.BatchVerifier("python")
    monkeypatch.setattr(verifier, "_default", fake)
    fake.stats["jax_sigs"] = 32_768
    return fake


@pytest.mark.parametrize("built, shared, want", [
    (0, 0, None), (4096, 0, 0.0), (64, 64, 50.0), (0, 64, 100.0),
    (4096, 64 * 4096, 100.0 * 64 / 65)])
def test_the_share_is_shared_over_shared_and_built(
        family, sent_to_a_device, built, shared, want):
    family.labels("built").inc(built)
    family.labels("shared").inc(shared)
    got = reader.read(None)
    assert got is None if want is None else got == pytest.approx(want)


def test_commits_decoded_by_the_program_are_what_it_reads(
        family, sent_to_a_device):
    from tendermint_tpu.types import (BlockID, Commit, PartSetHeader, Vote)
    bid = BlockID(b"B" * 32, PartSetHeader(1, b"p" * 32))
    o = Commit(bid, [Vote(bytes(20), i, 3, 0, 7, 2, bid, bytes(64))
                     for i in range(64)]).to_obj()
    for _ in range(8):
        Commit.from_obj(o)
    assert reader.read(None) == pytest.approx(100.0 * 64 / 65)


def test_a_program_without_the_family_or_without_a_device_reads_nothing(
        monkeypatch, family, sent_to_a_device):
    from tendermint_tpu import telemetry
    family.labels("shared").inc(64)
    family.labels("built").inc(1)
    assert reader.read(None) == pytest.approx(100.0 * 64 / 65)
    sent_to_a_device.stats["jax_sigs"] = 0      # a rehearsal on the host
    assert reader.read(None) is None
    sent_to_a_device.stats["jax_sigs"] = 32_768
    # the parent commit: the registry has no such family
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    assert program_spans.counter_total(FAMILY) is None
    assert reader.read(None) is None


def test_the_lite_rehearsal_counts_and_leaves_the_metric_out(family):
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    # host-verified batches: nothing went to a device, the line says so
    assert NAME not in line["metrics"]
    assert "lite_collect_votes_share" in line["metrics"]
    # yet every commit the cell decoded was counted: one id built, and
    # every vote of several validators handed it
    built, shared = (family.labels(how).value for how in ("built", "shared"))
    assert built > 0 and shared >= 2 * built


def test_the_lite_line_of_a_program_without_the_counter(monkeypatch, family):
    """The parent commit with these files laid over it: a line, with
    this metric left out and the rest as they were."""
    from tendermint_tpu import telemetry
    monkeypatch.delitem(telemetry.REGISTRY._families, FAMILY)
    line = rehearse(LITE, trace=True)
    assert line["correct"] is True
    assert NAME not in line["metrics"]
    assert line["metrics"]["lite_collect_share"]["value"] > 0
