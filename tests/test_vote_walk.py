"""The vote walk of ValidatorSet.commit_verification_items, native
(native/prep.cpp walk_votes, one call a commit) against pure (types/
validator_set._walk_votes, the specification): one matrix of commits,
each walked both ways and held equal in what the verifier gets
(SigColumns: pk, the signature OBJECTS, msgs, idx) and what the judge
gets (CommitPower: powers, for_block, tally), or in the ValueError's
text; which way a commit went (tm_verifier_vote_walks_total{how}); two
broken walks that the matrix must catch; and a process without the
extension."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from tendermint_tpu import native, telemetry
from tendermint_tpu.types import (BlockID, Commit, PartSetHeader, Validator,
                                  ValidatorSet, Vote)
from tendermint_tpu.types import validator_set as vs_mod
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.vote import VoteType

CHAIN = "walk-chain"
HEIGHT = 7
BLOCK = BlockID(b"B" * 32, PartSetHeader(1, b"p" * 32))
OTHER = BlockID(b"C" * 32, PartSetHeader(2, b"q" * 32))
NIL = BlockID()
PRE = VoteType.PRECOMMIT


def native_loaded() -> bool:
    return native.walk_votes([], HEIGHT, 0, PRE, None) is not None


@pytest.fixture
def needs_native():
    if not native_loaded():
        pytest.skip("no native vote walk on this host")


def fresh(bid: BlockID) -> BlockID:
    """An equal BlockID of its own objects, as a wire-parsed vote has."""
    return BlockID(bytes(bytearray(bid.hash)),
                   PartSetHeader(bid.parts.total,
                                 bytes(bytearray(bid.parts.hash))))


def toy_set(n: int, key_len: int = 32) -> ValidatorSet:
    """n validators with made-up keys and unequal stake: the collect
    phase never looks inside a key or a signature."""
    return ValidatorSet([
        Validator(hashlib.sha512(b"key%d" % i).digest()[:key_len],
                  10 + i % 7) for i in range(n)])


def toy_commit(n, bid_of=lambda i: BLOCK, ts_of=lambda i: 1000,
               absent=(), edit=None, precommits=list):
    """A commit of n slots; `edit(i, vote)` may change vote i's fields."""
    votes = []
    for i in range(n):
        if i in absent:
            votes.append(None)
            continue
        v = Vote(b"a" * 20, i, HEIGHT, 0, ts_of(i), PRE, bid_of(i),
                 signature=hashlib.sha512(b"sig%d" % i).digest())
        if edit is not None:
            edit(i, v)
        votes.append(v)
    return Commit(BLOCK, precommits(votes))


def set_field(at: int, name: str, value):
    def edit(i, vote):
        if i == at:
            setattr(vote, name, value)
    return edit


def ts_each(i):
    return 1_700_000_000_000_000_000 + 7_919 * i


def mixed_ids(i):
    return (BLOCK, NIL, OTHER, BLOCK, fresh(NIL))[i % 5]


# name -> (validators, toy_commit's keywords); every case is walked both
# ways and compared, whatever it does
MATRIX = {
    "one_ts": (100, {}),
    "ts_a_vote": (100, {"ts_of": ts_each}),
    "ts_in_runs": (100, {"ts_of": lambda i: 1000 + i // 7}),
    "absent_head": (100, {"ts_of": ts_each, "absent": (0, 1)}),
    "absent_middle": (100, {"ts_of": ts_each, "absent": (41, 42, 77)}),
    "absent_tail": (100, {"ts_of": ts_each, "absent": (98, 99)}),
    "absent_all_but_one": (4, {"absent": (0, 1, 3)}),
    "absent_all": (4, {"absent": (0, 1, 2, 3)}),
    "nil_votes": (100, {"bid_of": lambda i: NIL if i % 9 == 4 else BLOCK}),
    "nil_votes_ts_a_vote": (100, {"bid_of": lambda i: NIL if i % 9 == 4
                                  else BLOCK, "ts_of": ts_each}),
    "other_block_ids": (100, {"bid_of": mixed_ids, "ts_of": ts_each,
                              "absent": (5, 50)}),
    "none_for_block": (4, {"bid_of": lambda i: OTHER}),
    "own_block_id_a_vote": (100, {"bid_of": lambda i: fresh(BLOCK),
                                  "ts_of": ts_each}),
    "own_nil_a_vote": (100, {"bid_of": lambda i: fresh(NIL) if i % 2
                             else fresh(BLOCK)}),
    "wrong_type_first": (100, {"edit": set_field(0, "type", 1)}),
    "wrong_type_last": (100, {"edit": set_field(99, "type", 1)}),
    "wrong_type_after_absent": (100, {"absent": (10,),
                                      "edit": set_field(11, "type", 1)}),
    "wrong_height_second": (100, {"absent": (0,),
                                  "edit": set_field(2, "height", 8)}),
    "wrong_height_last": (100, {"edit": set_field(99, "height", 6)}),
    "wrong_height_after_absent": (100, {"absent": (60, 61), "edit":
                                        set_field(62, "height", 8)}),
    "wrong_round_middle": (100, {"edit": set_field(50, "round", 1)}),
    "wrong_round_last": (100, {"edit": set_field(99, "round", 3)}),
    "wrong_round_after_absent": (100, {"absent": (98,), "edit":
                                       set_field(99, "round", 1)}),
    "wrong_type_and_height": (4, {"edit": lambda i, v: (
        setattr(v, "type", 1), setattr(v, "height", 9)) if i == 2
        else None}),
    "wrong_height_before_wrong_type": (4, {"edit": lambda i, v: setattr(
        v, *(("height", 9) if i == 1 else ("type", 1)
             if i == 2 else ("round", 0)))}),
    "wrong_type_beyond_a_vote_declined": (4, {
        "ts_of": lambda i: 2 ** 70 if i == 1 else i,
        "edit": set_field(3, "type", 1)}),
    "ts_zero": (4, {"ts_of": lambda i: 0}),
    "ts_negative": (4, {"ts_of": lambda i: -1 - i}),
    "ts_int64_ends": (4, {"ts_of": lambda i: (2 ** 63 - 1, -2 ** 63,
                                              2 ** 63 - 1, 0)[i]}),
    "ts_2_70": (100, {"ts_of": lambda i: 2 ** 70 if i == 37 else i}),
    "ts_2_63": (4, {"ts_of": lambda i: 2 ** 63}),
    "ts_bool": (4, {"ts_of": lambda i: True}),
    "ts_float": (4, {"ts_of": lambda i: 1.5}),
    "type_float": (4, {"edit": set_field(1, "type", 2.0)}),
    "signature_bytearray": (4, {"edit": set_field(
        2, "signature", bytearray(64))}),
    "signature_str": (4, {"edit": set_field(0, "signature", "s" * 64)}),
    "signature_short": (4, {"edit": set_field(3, "signature", b"")}),
    "hash_bytearray": (4, {"bid_of": lambda i: BlockID(
        bytearray(b"B" * 32), PartSetHeader(1, b"p" * 32))}),
    "precommits_tuple": (100, {"ts_of": ts_each, "precommits": tuple}),
    "secp256k1_set": (100, {"ts_of": ts_each, "absent": (3,),
                            "key_len": 33}),
    "secp256k1_one_ts": (4, {"key_len": 33}),
    "size_1": (1, {}),
    "size_4": (4, {"ts_of": ts_each}),
    "size_10000": (10_000, {"ts_of": ts_each, "absent": (0, 5_000, 9_999),
                            "bid_of": lambda i: NIL if i % 1_000 == 7
                            else BLOCK}),
    "size_10000_one_ts": (10_000, {}),
}
# the cases the native walk hands back to the loop
DECLINED = {"ts_2_70", "ts_2_63", "ts_bool", "ts_float", "type_float",
            "signature_bytearray", "signature_str", "hash_bytearray",
            "precommits_tuple", "wrong_type_beyond_a_vote_declined"}
NOT_PRECOMMIT = "ValueError: commit contains non-precommit"
MISMATCH = "ValueError: commit vote height/round mismatch"
# the cases that raise, and what
RAISES = {
    "wrong_type_first": NOT_PRECOMMIT, "wrong_type_last": NOT_PRECOMMIT,
    "wrong_type_after_absent": NOT_PRECOMMIT,
    "wrong_type_and_height": NOT_PRECOMMIT,
    "wrong_type_beyond_a_vote_declined": NOT_PRECOMMIT,
    "wrong_height_second": MISMATCH, "wrong_height_last": MISMATCH,
    "wrong_height_after_absent": MISMATCH,
    "wrong_height_before_wrong_type": MISMATCH,
    "wrong_round_middle": MISMATCH, "wrong_round_last": MISMATCH,
    "wrong_round_after_absent": MISMATCH,
    # no vote, so no height: refused before the walk, both ways
    "absent_all": "ValueError: commit height mismatch",
    # the loop's dict of block ids cannot hold it: its error, both ways
    "hash_bytearray": "TypeError: unhashable type: 'bytearray'",
}


def build(case):
    n, kw = MATRIX[case]
    kw = dict(kw)
    return toy_set(n, kw.pop("key_len", 32)), toy_commit(n, **kw)


def outcome(valset, commit, height=HEIGHT):
    """What commit_verification_items leaves, in a form that compares:
    the error's text, or every field of both results."""
    try:
        items, power = valset.commit_verification_items(
            CHAIN, BLOCK, height, commit)
    except (ValueError, TypeError) as e:
        return ("raised", f"{type(e).__name__}: {e}")
    if isinstance(items, SigColumns):
        lanes = ("columns", items.pk.tobytes(), items.pk.shape,
                 list(map(id, items.sigs)), items.msgs,
                 items.idx.dtype.str, items.idx.tolist())
    else:
        lanes = ("triples", [(k, m, id(s)) for k, m, s in items])
    return lanes + (power.powers.dtype.str, power.powers.tolist(),
                    power.for_block.dtype.str, power.for_block.tolist(),
                    power.tally)


@pytest.fixture
def vote_walks():
    """tm_verifier_vote_walks_total, telemetry on and both children
    counting from zero; as found afterwards."""
    fam = telemetry.REGISTRY.get("verifier_vote_walks_total")
    assert fam is vs_mod._m_vote_walks
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    held = {how: fam.labels(how).value for how in ("native", "pure")}
    for how in held:
        fam.labels(how).value = 0.0
    yield lambda: {how: fam.labels(how).value for how in held}
    for how, value in held.items():
        fam.labels(how).value = value
    telemetry.set_enabled(was)


def pure_only(monkeypatch):
    monkeypatch.setattr(native, "walk_votes", lambda *a: None)


@pytest.mark.parametrize("case", sorted(MATRIX))
def test_native_walk_equals_pure(case, needs_native, monkeypatch,
                                 vote_walks):
    valset, commit = build(case)
    got = outcome(valset, commit)
    walked = float(case not in RAISES)      # counted at a walk's end
    assert vote_walks() == {"native": walked * (case not in DECLINED),
                            "pure": walked * (case in DECLINED)}
    pure_only(monkeypatch)
    assert outcome(valset, commit) == got
    if case in RAISES:
        assert got == ("raised", RAISES[case])
    else:
        assert got[0] != "raised"


@pytest.mark.parametrize("field,value,text", [
    ("type", 1, "commit contains non-precommit"),
    ("height", 8, "commit vote height/round mismatch"),
    ("round", 1, "commit vote height/round mismatch"),
])
@pytest.mark.parametrize("at", [0, 99])
def test_both_walks_refuse_a_vote_with_the_same_words(
        field, value, text, at, needs_native):
    """At the walk itself, where vote 0's height and round can be wrong
    (commit_verification_items takes its height and round from it)."""
    _, commit = build("ts_a_vote")
    setattr(commit.precommits[at], field, value)
    for walk in (native.walk_votes, vs_mod._walk_votes):
        with pytest.raises(ValueError) as e:
            walk(commit.precommits, HEIGHT, 0, PRE,
                 lambda b: ("p", "s", True))
        assert str(e.value) == text


def test_the_matrix_declines_what_it_says(needs_native):
    """DECLINED is what the native walk returns None for, no more."""
    def template(b):
        return ("pre", "suf", b == BLOCK)
    for case in MATRIX:
        if case in RAISES and case not in DECLINED:
            continue
        _, commit = build(case)
        walked = native.walk_votes(commit.precommits, HEIGHT, 0, PRE,
                                   template)
        assert (walked is None) == (case in DECLINED), case


@pytest.mark.parametrize("what,args", [
    ("height_2_70", (2 ** 70, 0, PRE)),
    ("round_bool", (HEIGHT, False, PRE)),
    ("precommit_str", (HEIGHT, 0, "2")),
])
def test_native_walk_declines_arguments_it_cannot_compare(
        what, args, needs_native):
    _, commit = build("one_ts")
    assert native.walk_votes(commit.precommits, *args,
                             lambda b: ("p", "s", True)) is None


def test_native_walk_asks_once_per_distinct_block_id(needs_native):
    """A commit whose votes each hold a BlockID of their own calls back
    once per distinct id, and gets the callback's own error."""
    asked = []

    def template(b):
        asked.append(b)
        return ("<", ">", b == BLOCK)
    _, commit = build("own_nil_a_vote")
    sigs, msgs, idx, for_block, absent, all_for = native.walk_votes(
        commit.precommits, HEIGHT, 0, PRE, template)
    assert [b == BLOCK for b in asked] == [True, False]
    assert msgs == [b"<1000>"] * 100 and idx.tolist() == list(range(100))
    assert for_block.tolist() == [i % 2 == 0 for i in range(100)]
    assert not all_for and absent == []

    def broken(b):
        raise KeyError("no layout")
    with pytest.raises(KeyError, match="no layout"):
        native.walk_votes(commit.precommits, HEIGHT, 0, PRE, broken)
    # a template of another shape: the loop's to report
    assert native.walk_votes(commit.precommits, HEIGHT, 0, PRE,
                             lambda b: (b"<", b">", True)) is None


def test_native_walk_shares_the_votes_own_signatures(needs_native):
    _, commit = build("absent_middle")
    sigs = native.walk_votes(commit.precommits, HEIGHT, 0, PRE,
                             lambda b: ("p", "s", True))[0]
    assert all(s is v.signature for s, v in zip(
        sigs, (v for v in commit.precommits if v is not None)))


# -- the control: broken walks the matrix must catch -----------------------

def skips_block_id_compare(pcs, height, round_, precommit, template):
    """A walk that takes the first vote's block id for every vote."""
    first = next(v for v in pcs if v is not None).block_id
    return vs_mod._walk_votes(
        [v and Vote(v.validator_address, v.validator_index, v.height,
                    v.round, v.timestamp_ns, v.type, first, v.signature)
         for v in pcs], height, round_, precommit, template)


def reuses_sign_bytes(pcs, height, round_, precommit, template):
    """A walk that splices the first vote's timestamp for every vote."""
    ts = next(v for v in pcs if v is not None).timestamp_ns
    return vs_mod._walk_votes(
        [v and Vote(v.validator_address, v.validator_index, v.height,
                    v.round, ts, v.type, v.block_id, v.signature)
         for v in pcs], height, round_, precommit, template)


@pytest.mark.parametrize("broken,caught_by", [
    (skips_block_id_compare, "nil_votes"),
    (reuses_sign_bytes, "ts_a_vote"),
])
def test_the_matrix_catches_a_broken_walk(broken, caught_by, monkeypatch):
    def both(case):
        valset, commit = build(case)
        monkeypatch.setattr(native, "walk_votes", broken)
        got = outcome(valset, commit)
        pure_only(monkeypatch)
        return got, outcome(valset, commit)
    got, want = both(caught_by)
    assert got != want
    # and by no case that lacks the trait: there the broken walk is right
    got, want = both("one_ts")
    assert got == want


# -- the loader's contract -------------------------------------------------

_CHILD = """
import hashlib, json, sys
sys.path.insert(0, {tests!r})
import test_vote_walk as t
from tendermint_tpu import native, telemetry
telemetry.set_enabled(True)
assert native.walk_votes([], 7, 0, 2, None) is None
out = {{}}
for case in {cases!r}:
    got = t.outcome(*t.build(case))
    # the signature objects' ids are a process's own: their bytes here
    out[case] = hashlib.sha256(repr(t.portable(got)).encode()).hexdigest()
fam = telemetry.REGISTRY.get("verifier_vote_walks_total")
print(json.dumps({{"digests": out, "native": fam.labels("native").value,
                  "pure": fam.labels("pure").value}}))
"""


def portable(got):
    """`outcome` without what only one process can compare."""
    if got[0] == "columns":
        return got[:3] + got[4:]
    if got[0] == "triples":
        return ("triples", [(k, m) for k, m, _ in got[1]]) + got[2:]
    return got


def test_without_the_extension_every_walk_is_pure_and_equal():
    cases = ["one_ts", "ts_a_vote", "nil_votes_ts_a_vote",
             "other_block_ids", "wrong_round_after_absent", "ts_2_70",
             "secp256k1_set", "size_4"]
    env = dict(os.environ, TM_TPU_NO_NATIVE="1", JAX_PLATFORMS="cpu")
    child = subprocess.run(
        [sys.executable, "-c", _CHILD.format(
            tests=os.path.dirname(os.path.abspath(__file__)), cases=cases)],
        env=env, capture_output=True, text=True, timeout=120)
    assert child.returncode == 0, child.stderr[-2000:]
    said = json.loads(child.stdout.strip().splitlines()[-1])
    assert said["native"] == 0.0
    assert said["pure"] == len(cases) - 1       # one of them raises
    for case in cases:
        want = hashlib.sha256(repr(portable(
            outcome(*build(case)))).encode()).hexdigest()
        assert said["digests"][case] == want, case


@pytest.mark.parametrize("path", ["native", "pure"])
def test_sign_bytes_match_vote_sign_bytes_on_both_paths(path, monkeypatch):
    """tests/test_types.py::test_commit_items_sign_bytes_match_vote_
    sign_bytes, with the walk named: each lane's sign-bytes are the
    vote's own Vote.sign_bytes."""
    if path == "pure":
        pure_only(monkeypatch)
    elif not native_loaded():
        pytest.skip("no native vote walk on this host")
    import test_types
    test_types.test_commit_items_sign_bytes_match_vote_sign_bytes()
    valset, commit = build("other_block_ids")
    items, _ = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                commit)
    assert [m for _, m, _ in items] == [
        v.sign_bytes(CHAIN) for v in commit.precommits if v is not None]


def test_every_walk_is_counted_once(vote_walks, monkeypatch):
    valset, commit = build("ts_a_vote")
    for _ in range(3):
        valset.commit_verification_items(CHAIN, BLOCK, HEIGHT, commit)
    loaded = native_loaded()
    assert vote_walks() == {"native": 3.0 * loaded,
                            "pure": 3.0 * (not loaded)}
    pure_only(monkeypatch)
    valset.commit_verification_items(CHAIN, BLOCK, HEIGHT, commit)
    assert vote_walks()["pure"] == 3.0 * (not loaded) + 1.0
