"""Commit.from_obj decodes a commit's votes onto one BlockID per distinct
block id (types/block.py): which objects are shared and which are not,
that the decoded commit is the per-vote decode's in every field and
byte, that the vote walk reads both forms alike, that a replaced id
stays its vote's own, how many objects the collector is left to track,
the counter of built and shared ids, and that nothing in the package
writes a field of a BlockID or PartSetHeader in place (shared objects
would show it in the siblings)."""

import ast
import gc
import hashlib
import json
import os

import numpy as np
import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.types import (BlockID, Commit, PartSetHeader, Validator,
                                  ValidatorSet, Vote)
from tendermint_tpu.types import block as block_mod
from tendermint_tpu.types import encoding
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.vote import VoteType

CHAIN = "sharing-chain"
HEIGHT = 9
BLOCK = BlockID(b"B" * 32, PartSetHeader(1, b"p" * 32))
OTHER = BlockID(b"C" * 32, PartSetHeader(2, b"q" * 32))
NIL = BlockID()
PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tendermint_tpu")

# name -> (validators, block id of vote i, absent validators)
COMMITS = {
    "64_votes_for_the_block": (64, lambda i: BLOCK, ()),
    "nil_other_block_and_absent": (
        23, lambda i: (BLOCK, BLOCK, NIL, OTHER, BLOCK)[i % 5], {0, 7, 22}),
    "one_vote": (1, lambda i: BLOCK, ()),
}


def toy_set(n: int) -> ValidatorSet:
    return ValidatorSet([
        Validator(hashlib.sha256(b"key%d" % i).digest(), 1 + i % 7)
        for i in range(n)])


def wire_of(name):
    """(valset, the commit's wire object, its canonical bytes): the
    object as a reader of bytes gets it, through the encoding."""
    n, bid_of, absent = COMMITS[name]
    valset = toy_set(n)
    votes = [None if i in absent else
             Vote(val.address, i, HEIGHT, 0, 5000 + i // 4,
                  VoteType.PRECOMMIT, bid_of(i),
                  signature=bytes([i % 251]) * 64)
             for i, val in enumerate(valset.validators)]
    raw = encoding.cdumps(Commit(BLOCK, votes).to_obj())
    return valset, encoding.cloads(raw), raw


def per_vote_decode(o) -> Commit:
    """What Commit.from_obj did before: every vote through the single
    vote's decode, a BlockID and a PartSetHeader each."""
    return Commit(BlockID.from_obj(o["block_id"]),
                  [Vote.from_obj(v) if v else None for v in o["precommits"]])


@pytest.mark.parametrize("name", sorted(COMMITS))
def test_equal_ids_are_one_object_and_distinct_ids_are_not(name):
    _, o, _ = wire_of(name)
    commit = Commit.from_obj(o)
    n, bid_of, absent = COMMITS[name]
    assert len(commit.precommits) == n
    first = {}                      # block id -> the object first seen
    first[commit.block_id] = commit.block_id
    for i, pc in enumerate(commit.precommits):
        if i in absent:
            assert pc is None
            continue
        assert pc.block_id == bid_of(i)
        assert pc.block_id is first.setdefault(pc.block_id, pc.block_id)
    assert len({id(b) for b in first.values()}) == len(first) == \
        len({bid_of(i) for i in range(n) if i not in absent} | {BLOCK})
    # and nothing is kept from one call to the next
    again = Commit.from_obj(o)
    assert again.block_id is not commit.block_id
    assert again.block_id.parts is not commit.block_id.parts


@pytest.mark.parametrize("name", sorted(COMMITS))
def test_the_decoded_commit_is_the_per_vote_decodes(name):
    _, o, raw = wire_of(name)
    got, want = Commit.from_obj(o), per_vote_decode(o)
    assert got.block_id == want.block_id
    assert type(got.block_id.parts.total) is int
    for a, b in zip(got.precommits, want.precommits, strict=True):
        if b is None:
            assert a is None
            continue
        for f in ("validator_address", "validator_index", "height", "round",
                  "timestamp_ns", "type", "signature"):
            assert getattr(a, f) == getattr(b, f)
            assert type(getattr(a, f)) is type(getattr(b, f))
        assert (a.block_id.hash, a.block_id.parts.total,
                a.block_id.parts.hash) == (
            b.block_id.hash, b.block_id.parts.total, b.block_id.parts.hash)
        assert a.block_id.key() == b.block_id.key()
        assert a.sign_bytes(CHAIN) == b.sign_bytes(CHAIN)
    assert got.to_obj() == want.to_obj() == o
    assert got.to_bytes() == want.to_bytes() == raw
    assert got.hash() == want.hash()
    assert (got.height(), got.round()) == (want.height(), want.round())


@pytest.mark.parametrize("name", sorted(COMMITS))
def test_the_vote_walk_reads_both_forms_alike(name):
    valset, o, _ = wire_of(name)
    got, want = (valset.commit_verification_items(CHAIN, BLOCK, HEIGHT, c)
                 for c in (Commit.from_obj(o), per_vote_decode(o)))
    (items, power), (items_w, power_w) = got, want
    assert isinstance(items, SigColumns) and isinstance(items_w, SigColumns)
    assert np.array_equal(items.pk, items_w.pk)
    assert items.sigs == items_w.sigs and items.msgs == items_w.msgs
    assert np.array_equal(items.idx, items_w.idx)
    assert items.idx.dtype == items_w.idx.dtype
    assert np.array_equal(power.powers, power_w.powers)
    assert np.array_equal(power.for_block, power_w.for_block)
    assert power.tally == power_w.tally
    assert list(items) == list(items_w)


@pytest.mark.parametrize("name", sorted(COMMITS))
def test_replacing_one_votes_block_id_leaves_its_siblings(name):
    _, o, _ = wire_of(name)
    commit = Commit.from_obj(o)
    present = [pc for pc in commit.precommits if pc is not None]
    before = [(pc.block_id, pc.block_id.key()) for pc in present]
    own = (commit.block_id.hash, commit.block_id.parts.total,
           commit.block_id.parts.hash)
    swapped = BlockID(b"Z" * 32, PartSetHeader(3, b"z" * 32))
    present[0].block_id = swapped
    assert present[0].block_id is swapped
    for pc, (bid, key) in list(zip(present, before))[1:]:
        assert pc.block_id is bid and pc.block_id.key() == key
    assert (commit.block_id.hash, commit.block_id.parts.total,
            commit.block_id.parts.hash) == own
    # the commit sees the replaced id (its caches are keyed on the
    # votes' fields), and only in that vote
    now = commit.to_obj()["precommits"]
    k = commit.precommits.index(present[0])
    assert now[k]["block_id"] == swapped.to_obj()
    assert [v for i, v in enumerate(now) if i != k] == \
        [v for i, v in enumerate(o["precommits"]) if i != k]


def test_a_64_vote_commit_leaves_the_collector_two_objects_a_vote():
    """Per vote: the Vote, and at most one more (no BlockID, no
    PartSetHeader, no instance dict). The per-vote decode, counted the
    same way, shows what the bound is not."""
    _, o, _ = wire_of("64_votes_for_the_block")

    def tracked_after(decode) -> int:
        was = gc.isenabled()
        gc.disable()                # no collection untracks or frees
        try:                        # anything between the two counts
            before = len(gc.get_objects())
            commit = decode(o)
            after = len(gc.get_objects())
            assert len(commit.precommits) == 64
            return after - before
        finally:
            if was:
                gc.enable()

    shared = tracked_after(Commit.from_obj)
    per_vote = tracked_after(per_vote_decode)
    assert shared <= 2 * 64, (shared, per_vote)
    assert per_vote >= shared + 2 * 63, (shared, per_vote)


@pytest.fixture
def counted():
    """The family, telemetry on, counting from zero."""
    fam = telemetry.REGISTRY.get("verifier_commit_block_ids_total")
    assert fam is block_mod._m_block_ids
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    held = {how: fam.labels(how).value for how in ("built", "shared")}
    for how in held:
        fam.labels(how).value = 0.0
    yield lambda: tuple(fam.labels(how).value for how in ("built", "shared"))
    for how, value in held.items():
        fam.labels(how).value = value
    telemetry.set_enabled(was)


@pytest.mark.parametrize("name, built, shared", [
    ("64_votes_for_the_block", 1, 64),
    ("nil_other_block_and_absent", 3, 18),
    ("one_vote", 1, 1)])
def test_the_counter_takes_a_commits_two_totals(counted, name, built, shared):
    _, o, _ = wire_of(name)
    Commit.from_obj(o)
    assert counted() == (built, shared)
    Commit.from_obj(o)
    assert counted() == (2 * built, 2 * shared)
    Vote.from_obj(o["precommits"][-1] or o["precommits"][-2])
    BlockID.from_obj(o["block_id"])     # a single vote counts neither
    assert counted() == (2 * built, 2 * shared)
    telemetry.set_enabled(False)
    Commit.from_obj(o)
    telemetry.set_enabled(True)
    assert counted() == (2 * built, 2 * shared)


def test_a_commit_of_nothing_builds_its_own_id_alone(counted):
    commit = Commit.from_obj(Commit().to_obj())
    assert commit.block_id == NIL and commit.precommits == []
    assert counted() == (1, 0)


@pytest.mark.parametrize("total", [True, 1.0])
def test_a_total_that_only_equals_the_blocks_is_not_shared(total):
    """1 == 1.0 == True as dict keys, but only 1 signs as "total":1: a
    vote with such a total keeps a BlockID of its own, and with it the
    sign-bytes nobody signed."""
    _, o, _ = wire_of("64_votes_for_the_block")
    o = json.loads(json.dumps(o))
    o["precommits"][5]["block_id"]["parts"]["total"] = total
    got, want = Commit.from_obj(o), per_vote_decode(o)
    odd = got.precommits[5]
    assert odd.block_id is not got.block_id
    assert type(odd.block_id.parts.total) is type(total)
    assert got.precommits[4].block_id is got.block_id
    assert got.precommits[6].block_id is got.block_id
    assert [pc.sign_bytes(CHAIN) for pc in got.precommits] == \
        [pc.sign_bytes(CHAIN) for pc in want.precommits]
    assert odd.sign_bytes(CHAIN) != got.precommits[4].sign_bytes(CHAIN)
    assert got.to_obj() == want.to_obj()


# -- no in-place write to a shared object ------------------------------------

_ID_FIELDS = {"hash", "parts", "total"}
_ID_NAMES = ("block_id", "bid", "parts", "psh", "part_set_header",
             "parts_header", "last_block_id")


def _in_place_writes(tree):
    """(line, source form) of every assignment, augmented assignment or
    `setattr` whose target is a field of something that is named as a
    block id or a part-set header is (`x.block_id.hash = ...`,
    `bid.parts = ...`, `header.parts.total += 1`), outside the bodies
    of BlockID and PartSetHeader."""
    own = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and \
                node.name in ("BlockID", "PartSetHeader"):
            own.update(id(n) for n in ast.walk(node))

    def names_an_id(node) -> bool:
        last = node.attr if isinstance(node, ast.Attribute) else \
            node.id if isinstance(node, ast.Name) else ""
        return last.lower().endswith(_ID_NAMES)

    def targets(node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                yield from (t.elts if isinstance(t, (ast.Tuple, ast.List))
                            else [t])
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            yield node.target

    for node in ast.walk(tree):
        if id(node) in own:
            continue
        for t in targets(node):
            if isinstance(t, ast.Attribute) and t.attr in _ID_FIELDS \
                    and names_an_id(t.value):
                yield node.lineno, ast.unparse(t)
        if isinstance(node, ast.Call) and \
                getattr(node.func, "id", "") == "setattr" and \
                len(node.args) == 3 and names_an_id(node.args[0]) and \
                getattr(node.args[1], "value", None) in _ID_FIELDS:
            yield node.lineno, ast.unparse(node)


def test_the_walk_finds_the_writes_it_is_there_for():
    src = ("class BlockID:\n"
           "    def f(self):\n"
           "        self.parts.total = 1\n"          # its own body
           "vote.block_id.hash = b''\n"
           "commit.block_id.parts = p\n"
           "bid.parts.total += 1\n"
           "a, pc.block_id.parts.hash = 1, b''\n"
           "setattr(header.last_block_id, 'hash', b'')\n"
           "vote.block_id = other\n"                 # replaced: fine
           "self.parts = parts\n"                    # a field named parts
           "header.hash = h\n")
    assert sorted(_in_place_writes(ast.parse(src))) == [
        (4, "vote.block_id.hash"), (5, "commit.block_id.parts"),
        (6, "bid.parts.total"), (7, "pc.block_id.parts.hash"),
        (8, "setattr(header.last_block_id, 'hash', b'')")]


def test_no_source_writes_a_block_ids_field_in_place():
    """Ids are replaced, never mutated (BlockID.__setattr__'s comment):
    a decoded commit's votes share their BlockID, so a field written in
    place on one would show in the others."""
    found = []
    for root, _, files in os.walk(PACKAGE):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    tree = ast.parse(fh.read(), path)
                found += [f"{os.path.relpath(path, PACKAGE)}:{line}: {what}"
                          for line, what in _in_place_writes(tree)]
    assert found == []
