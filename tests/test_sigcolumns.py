"""A commit's signatures as columns (types/sigcolumns.py): what
ValidatorSet.commit_verification_items builds against the triples and
power pairs of the loop it replaced, native.prep_columns against
native.prep_items, BatchVerifier.verify_async on both forms, the stake
tally of check_commit_results, and the counter that says which form
reached the device."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu import native, telemetry
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.models.verifier import (
    MAX_PREP_THREADS as MAX_T, MIN_LANES_A_THREAD as MIN_LANES,
    BatchVerifier)
from tendermint_tpu.types import (BlockID, Commit, PartSetHeader, PrivKey,
                                  Validator, ValidatorSet, Vote)
from tendermint_tpu.types.keys import verify_any
from tendermint_tpu.types.vote import VoteType

CHAIN = "columns-chain"
HEIGHT = 7
BLOCK = BlockID(b"B" * 32, PartSetHeader(1, b"p" * 32))
OTHER = BlockID(b"C" * 32, PartSetHeader(2, b"q" * 32))
NIL = BlockID()
L = 2 ** 252 + 27742317777372353535851937790883648493


def fresh(bid: BlockID) -> BlockID:
    """An equal BlockID of its own objects, as a wire-parsed vote has."""
    return BlockID(bytes(bytearray(bid.hash)),
                   PartSetHeader(bid.parts.total,
                                 bytes(bytearray(bid.parts.hash))))


def toy_set(n: int, power=10) -> ValidatorSet:
    """n validators with made-up keys: the collect phase never looks
    inside a key or a signature."""
    return ValidatorSet([
        Validator(hashlib.sha256(b"key%d" % i).digest(),
                  power(i) if callable(power) else power)
        for i in range(n)])


def toy_commit(valset, bid_of, ts_of=lambda i: 1000, absent=(),
               height=HEIGHT, round_=0, type_=VoteType.PRECOMMIT):
    votes = []
    for i, val in enumerate(valset.validators):
        if i in absent:
            votes.append(None)
            continue
        votes.append(Vote(val.address, i, height, round_, ts_of(i), type_,
                          bid_of(i), signature=bytes([i % 251]) * 64))
    return Commit(BLOCK, votes)


def parents_loop(valset, block_id, commit):
    """The triples and (power, for_block) pairs of the per-vote loop
    this replaced: a sign-bytes string per vote through Vote.sign_bytes."""
    triples, pairs = [], []
    for val, pc in zip(valset.validators, commit.precommits):
        if pc is None:
            continue
        triples.append((val.pubkey, pc.sign_bytes(CHAIN), pc.signature))
        pairs.append((val.voting_power, pc.block_id == block_id))
    return triples, pairs


SHAPES = {
    "all_present_one_shared_block_id": dict(n=9, bid_of=lambda i: BLOCK),
    "a_block_id_object_per_vote": dict(n=9, bid_of=lambda i: fresh(BLOCK)),
    "absent_votes": dict(n=9, bid_of=lambda i: fresh(BLOCK),
                         absent={0, 4, 8}),
    "nil_votes_and_a_second_block_id": dict(
        n=11, bid_of=lambda i: fresh((BLOCK, BLOCK, NIL, OTHER)[i % 4])),
    "a_timestamp_per_vote": dict(n=9, bid_of=lambda i: fresh(BLOCK),
                                 ts_of=lambda i: 5000 + 17 * i),
    "timestamps_in_runs_with_absent_and_nil": dict(
        n=12, bid_of=lambda i: NIL if i in (5, 6) else BLOCK,
        ts_of=lambda i: 5000 + i // 3, absent={3, 7}),
    "the_same_block_id_fields_but_another_part_set": dict(
        n=6, bid_of=lambda i: BlockID(BLOCK.hash, PartSetHeader(
            1 + i % 2, BLOCK.parts.hash))),
    "every_vote_for_nil": dict(n=5, bid_of=lambda i: NIL),
    "one_validator": dict(n=1, bid_of=lambda i: fresh(BLOCK)),
    "ten_thousand_validators": dict(
        n=10_000, bid_of=lambda i: fresh(BLOCK),
        ts_of=lambda i: HEIGHT * 10 ** 9 + i, absent={17, 9_999}),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_columns_equal_the_triples_of_the_loop_they_replace(shape):
    spec = dict(SHAPES[shape])
    valset = toy_set(spec.pop("n"), power=lambda i: 1 + i % 7)
    commit = toy_commit(valset, **spec)
    want, pairs = parents_loop(valset, BLOCK, commit)
    items, power = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                    commit)
    assert isinstance(items, SigColumns)
    n = len(want)
    assert len(items) == n and list(items) == want
    assert [items[k] for k in (0, n // 2, n - 1, -1, -n)] == \
        [want[k] for k in (0, n // 2, n - 1, -1, -n)]
    for k in (n, -n - 1):
        with pytest.raises(IndexError):
            items[k]
    for sl in (slice(0, n // 2), slice(n // 2, None), slice(1, -1),
               slice(0, 0), slice(None, None, 2)):
        part = items[sl]
        assert isinstance(part, SigColumns) and part.msgs is items.msgs
        assert len(part) == len(want[sl]) and list(part) == want[sl]
    assert items.pk.dtype == np.uint8 and items.idx.dtype == np.int32
    assert want[n // 2] in items and items.index(want[-1]) == n - 1
    # one sign-bytes string per run, not per vote
    runs = 1 + sum(1 for a, b in zip(want, want[1:]) if a[1] != b[1])
    assert len(items.msgs) == runs
    assert power.powers.tolist() == [p for p, _ in pairs]
    assert power.for_block.tolist() == [f for _, f in pairs]
    assert power.tally == sum(p for p, f in pairs if f)
    assert type(power.tally) is int


def test_concat_of_columns_and_of_lists():
    valset = toy_set(6)
    a = toy_commit(valset, lambda i: fresh(BLOCK), ts_of=lambda i: i // 2)
    b = toy_commit(valset, lambda i: fresh(OTHER), absent={2})
    parts, want = [], []
    for commit in (a, b, a):
        items, _ = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                    commit)
        parts.append(items)
        want += parents_loop(valset, BLOCK, commit)[0]
    whole = SigColumns.concat(parts)
    assert isinstance(whole, SigColumns) and list(whole) == want
    assert len(whole.msgs) == 3 + 1 + 3 and len(whole) == 17
    assert list(whole[4:13]) == want[4:13]
    assert list(SigColumns.concat([whole[:5], whole[5:]])) == want
    # a list among them (a secp256k1 set's commit): a list of triples
    mixed = SigColumns.concat([parts[0], list(parts[1]), parts[2]])
    assert type(mixed) is list and mixed == want
    assert SigColumns.concat([]) == []


@pytest.mark.parametrize("case, message", [
    ("short_commit", "commit size 3 != valset size 4"),
    ("another_height", "commit height mismatch"),
    ("a_prevote", "commit contains non-precommit"),
    ("a_vote_of_another_height", "commit vote height/round mismatch"),
    ("a_vote_of_another_round", "commit vote height/round mismatch"),
    ("round_before_type_in_vote_order", "commit vote height/round mismatch"),
    ("type_before_round_in_vote_order", "commit contains non-precommit"),
])
def test_structural_errors_keep_their_messages(case, message):
    valset = toy_set(4)
    commit = toy_commit(valset, lambda i: fresh(BLOCK))
    votes, height = commit.precommits, HEIGHT
    if case == "short_commit":
        del votes[-1]
    elif case == "another_height":
        height = HEIGHT + 1
    elif case == "a_prevote":
        votes[2].type = VoteType.PREVOTE
    elif case == "a_vote_of_another_height":
        votes[3].height = HEIGHT + 1
    elif case == "a_vote_of_another_round":
        votes[1].round = 1
    elif case == "round_before_type_in_vote_order":
        votes[1].round, votes[2].type = 1, VoteType.PREVOTE
    else:
        votes[1].type, votes[2].round = VoteType.PREVOTE, 1
    with pytest.raises(ValueError) as e:
        valset.commit_verification_items(CHAIN, BLOCK, height, commit)
    assert str(e.value) == message


# ------------------------------------------------- native.prep_columns --

def signed_batch(n, n_msgs=3):
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(n)]
    msgs = [b"message %d " % k * (1 + 20 * k) for k in range(n_msgs)]
    idx = np.array([i % n_msgs for i in range(n)], np.int32)
    sigs = [p.sign(msgs[j]) for p, j in zip(privs, idx)]
    pk = np.frombuffer(b"".join(p.pubkey.ed25519 for p in privs),
                       np.uint8).reshape(n, 32)
    return pk, sigs, msgs, idx


def s_plus_l(sig: bytes) -> bytes:
    s = int.from_bytes(sig[32:], "little") + L
    return sig[:32] + s.to_bytes(32, "little")


PREP_CASES = {
    "genuine": lambda pk, sigs, msgs, idx: None,
    "a_63_byte_signature": lambda pk, sigs, msgs, idx:
        sigs.__setitem__(2, sigs[2][:63]),
    "a_65_byte_signature": lambda pk, sigs, msgs, idx:
        sigs.__setitem__(0, sigs[0] + b"\0"),
    "s_at_or_above_L": lambda pk, sigs, msgs, idx:
        sigs.__setitem__(4, s_plus_l(sigs[4])),
    "a_zero_length_message": lambda pk, sigs, msgs, idx:
        msgs.__setitem__(1, b""),
    "a_message_past_the_stack_scratch": lambda pk, sigs, msgs, idx:
        msgs.__setitem__(0, b"x" * 5000),
    "a_flipped_signature_bit": lambda pk, sigs, msgs, idx:
        sigs.__setitem__(3, bytes([sigs[3][0] ^ 1]) + sigs[3][1:]),
}


@pytest.mark.parametrize("case", sorted(PREP_CASES))
def test_prep_columns_equals_prep_items_in_all_five_arrays(case):
    pk, sigs, msgs, idx = signed_batch(7)
    PREP_CASES[case](pk, sigs, msgs, idx)
    cols = SigColumns(pk, sigs, msgs, idx)
    got = native.prep_columns(pk, sigs, msgs, idx)
    want = native.prep_items(list(cols))
    assert got is not None and want is not None
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    bad = {"a_63_byte_signature": 2, "a_65_byte_signature": 0,
           "s_at_or_above_L": 4}.get(case)
    assert got[4].tolist() == [i != bad for i in range(7)]
    # h is SHA-512(R || A || M) mod L
    for i in range(7):
        if i == bad:
            continue
        h = hashlib.sha512(sigs[i][:32] + pk[i].tobytes()
                           + msgs[idx[i]]).digest()
        assert int.from_bytes(got[3][i].tobytes(), "little") == \
            int.from_bytes(h, "little") % L


def test_prep_columns_on_an_empty_batch_a_view_and_what_it_refuses():
    empty = native.prep_columns(np.zeros((0, 32), np.uint8), [], [],
                                np.zeros(0, np.int32))
    want = native.prep_items([])
    assert [a.shape for a in empty] == [a.shape for a in want] == \
        [(0, 32)] * 4 + [(0,)]
    pk, sigs, msgs, idx = signed_batch(6)
    whole = native.prep_columns(pk, sigs, msgs, idx)
    # a strided slice of the columns: not contiguous where it lies
    half = native.prep_columns(pk[::2], sigs[::2], msgs, idx[::2])
    for h, w in zip(half, whole):
        assert h.tobytes() == w[::2].tobytes()
    with pytest.raises(ValueError):
        native.prep_columns(pk, sigs, msgs[:1], idx)    # idx past msgs
    with pytest.raises(ValueError):
        native.prep_columns(pk[:5], sigs, msgs, idx)    # a short column
    # a member that is no bytes object: the general path's, as in a list
    assert native.prep_columns(pk, [bytearray(s) for s in sigs], msgs,
                               idx) is None
    assert native.prep_items([(pk[0].tobytes(), msgs[0],
                               bytearray(sigs[0]))]) is None


# ------------------------------------------------ verify_async(columns) --

N_DEVICE = 12           # one chunk, the 16 bucket


@pytest.fixture(scope="module")
def net():
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(N_DEVICE)]
    valset = ValidatorSet([Validator(p.pubkey.ed25519, 10) for p in privs])
    by_addr = {p.pubkey.address: p for p in privs}
    return valset, [by_addr[v.address] for v in valset.validators]


def signed_commit(net, n=None, tampered=()):
    """The first `n` validators' set and its commit, a timestamp per
    vote; the lanes in `tampered` carry a broken signature."""
    valset, privs = net
    if n is not None:
        privs = privs[:n]
        valset = ValidatorSet([Validator(p.pubkey.ed25519, 10)
                               for p in privs])
        by_addr = {p.pubkey.address: p for p in privs}
        privs = [by_addr[v.address] for v in valset.validators]
    votes = []
    for i, (val, priv) in enumerate(zip(valset.validators, privs)):
        v = Vote(val.address, i, HEIGHT, 0, 1000 + i % 3,
                 VoteType.PRECOMMIT, fresh(BLOCK))
        sig = priv.sign(v.sign_bytes(CHAIN))
        if i in tampered:
            k = (0, 40, 63)[i % 3]      # R, s, s's top byte (s >= L)
            sig = sig[:k] + bytes([sig[k] ^ 0x80]) + sig[k + 1:]
        v.signature = sig
        votes.append(v)
    return valset, Commit(BLOCK, votes)


@pytest.mark.parametrize("n, route", [(N_DEVICE, "device"), (6, "host")])
def test_verify_async_on_columns_and_on_their_list(net, n, route):
    """Above auto_threshold the columns go to the device (the jnp kernel
    here), at or under it to the host's scalar path as a list: the same
    verdicts lane by lane, which are the scalar oracle's."""
    tampered = {1, 2, 3, n - 1}
    valset, commit = signed_commit(net, n, tampered)
    items, power = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                    commit)
    verifier = BatchVerifier("auto", auto_threshold=8, mesh="off")
    before = dict(verifier.stats)
    from_columns = verifier.verify_async(items)()
    from_list = verifier.verify_async(list(items))()
    assert verifier.stats["jax_sigs"] - before["jax_sigs"] == \
        (2 * n if route == "device" else 0)
    oracle = [verify_any(*it) for it in items]
    assert from_columns.tolist() == from_list.tolist() == oracle
    assert oracle == [i not in tampered for i in range(n)]
    with pytest.raises(ValueError, match="invalid signature in commit"):
        valset.check_commit_results(from_columns, power)


def test_a_prep_forced_onto_two_threads_gives_the_same_verdicts(
        net, monkeypatch):
    """The thread count is a rule over lanes and cores; forced to 2 on a
    toy batch (which the rule would hash inline), the jnp kernels return
    the verdicts of the verifier left alone, on both forms."""
    from tendermint_tpu.models import verifier as verifier_mod
    tampered = {0, 5, N_DEVICE - 1}
    valset, commit = signed_commit(net, tampered=tampered)
    items, _ = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                commit)
    seen = []
    real = native.prep_columns, native.prep_items

    def watch(fn):
        def call(*args):
            seen.append(args[-1])
            return fn(*args)
        return call
    monkeypatch.setattr(native, "prep_columns", watch(real[0]))
    monkeypatch.setattr(native, "prep_items", watch(real[1]))
    verifier = BatchVerifier("jax", mesh="off")
    assert verifier_mod.prep_threads(N_DEVICE) == 1
    alone = [verifier.verify_async(form)()
             for form in (items, list(items))]
    monkeypatch.setattr(verifier_mod, "prep_threads", lambda n: 2)
    forced = [verifier.verify_async(form)()
              for form in (items, list(items))]
    assert seen == [1, 1, 2, 2]
    for got in alone + forced:
        assert got.tolist() == [i not in tampered for i in range(N_DEVICE)]


# lanes, usable cores -> threads of the native prep. One core is left to
# the rest of the process, so two cores hash inline as one does.
PREP_THREADS_TABLE = [
    (0, 13, 1), (1, 13, 1), (256, 13, 1), (2_000, 13, 1),
    (2 * MIN_LANES - 1, 13, 1), (2 * MIN_LANES, 13, 2),
    (3 * MIN_LANES, 13, 3), (8_192, 13, min(8_192 // MIN_LANES, MAX_T)),
    (10_000, 13, min(10_000 // MIN_LANES, MAX_T)), (32_768, 13, MAX_T),
    (10 ** 7, 13, MAX_T), (10 ** 7, 64, MAX_T),
    (32_768, 4, 3), (32_768, 3, 2), (10_000, 2, 1), (32_768, 2, 1),
] + [(n, 1, 1) for n in (0, 1, 2_048, 10_000, 32_768, 10 ** 7)]


@pytest.mark.parametrize("lanes, cores, threads", PREP_THREADS_TABLE)
def test_the_preps_thread_count_follows_lanes_and_cores(lanes, cores,
                                                        threads):
    from tendermint_tpu.models.verifier import prep_threads
    assert prep_threads(lanes, cores) == threads
    assert 1 <= threads <= max(1, cores - 1)


def test_the_cores_are_the_processs_own_and_read_once(monkeypatch):
    import os
    from tendermint_tpu.models import verifier as verifier_mod
    cores = len(os.sched_getaffinity(0))
    assert verifier_mod._usable_cores() == cores
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0})
    assert verifier_mod._usable_cores() == cores
    assert verifier_mod.prep_threads(10 ** 7) == min(MAX_T,
                                                     max(1, cores - 1))


@pytest.mark.parametrize("control", [None, "accept_all", "truncate"])
def test_the_harness_tap_on_verify_async_sees_columns(net, control):
    """benchmark/probe.VerifierTap replaces `verify_async` on the
    instance, takes `len(items)`, slices `items[:n // 2]` and passes
    them on: the one entry, whatever the form."""
    from benchmark import probe
    from benchmark.spans import SpanLog
    valset, commit = signed_commit(net, tampered={N_DEVICE - 2})
    verifier = BatchVerifier("python")
    spans = SpanLog()
    with probe.VerifierTap(verifier, spans, control):
        if control is None:
            with pytest.raises(ValueError, match="invalid signature"):
                valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit,
                                     verifier=verifier)
        else:       # the weakened verifier lets the bad lane through
            valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit,
                                 verifier=verifier)
    assert "verify_async" not in verifier.__dict__
    assert spans.count("verify_dispatch") == 1
    assert verifier.stats["sigs"] == {None: N_DEVICE, "accept_all": 0,
                                      "truncate": N_DEVICE // 2}[control]


def test_a_secp256k1_set_still_gets_a_list_and_still_verifies():
    from tendermint_tpu.types.keys import Secp256k1PrivKey
    keys = [Secp256k1PrivKey.generate(bytes([i + 0x40]) * 32)
            for i in range(4)]
    valset = ValidatorSet([Validator(k.pubkey.secp256k1, 10) for k in keys])
    by_addr = {k.pubkey.address: k for k in keys}
    votes = []
    for i, val in enumerate(valset.validators):
        v = Vote(val.address, i, HEIGHT, 0, 2000 + i, VoteType.PRECOMMIT,
                 BLOCK if i else NIL)
        v.signature = by_addr[val.address].sign(v.sign_bytes(CHAIN))
        votes.append(v)
    commit = Commit(BLOCK, votes)
    assert valset.columns().pk is None
    items, power = valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                    commit)
    assert type(items) is list
    assert items == parents_loop(valset, BLOCK, commit)[0]
    assert power.for_block.tolist() == [False, True, True, True]
    assert power.tally == 30
    for backend in ("python", "jax"):
        verifier = BatchVerifier(backend)
        assert verifier.verify(items).all()
        valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit,
                             verifier=verifier)
    votes[2].signature = votes[1].signature
    with pytest.raises(ValueError, match="invalid signature in commit"):
        valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit,
                             verifier=BatchVerifier("python"))


# -------------------------------------------------- check_commit_results --

def tally_case(powers, for_block):
    valset = toy_set(len(powers), power=lambda i: powers[i])
    by_power = [v.voting_power for v in valset.validators]
    chosen = set()
    for want in for_block:      # validators by their power, once each
        chosen.add(next(i for i, p in enumerate(by_power)
                        if p == want and i not in chosen))
    commit = toy_commit(valset,
                        lambda i: fresh(BLOCK if i in chosen else NIL))
    return valset, valset.commit_verification_items(CHAIN, BLOCK, HEIGHT,
                                                    commit)[1]


@pytest.mark.parametrize("ok_as", [np.array, list, tuple])
def test_check_takes_an_array_or_a_list_and_one_bad_lane_refuses(ok_as):
    valset, power = tally_case([10] * 6, [10] * 6)
    valset.check_commit_results(ok_as([True] * 6), power)
    for lane in (0, 5):
        ok = [True] * 6
        ok[lane] = False
        with pytest.raises(ValueError) as e:
            valset.check_commit_results(ok_as(ok), power)
        assert str(e.value) == "invalid signature in commit"


@pytest.mark.parametrize("ok_as", [np.array, list])
@pytest.mark.parametrize("verdicts, refusal", [
    (0, "insufficient voting power: 0/40"),
    (1, "insufficient voting power: 10/40"),
    (2, "insufficient voting power: 20/40"),    # half the lanes
    (3, None),              # 30 of 40 verified: what the parent's zip gave
    (4, None),
    (5, "invalid signature in commit"),         # a verdict with no lane
    (8, "invalid signature in commit"),
])
def test_a_lane_without_a_verdict_counts_no_power(ok_as, verdicts, refusal):
    """The judge must not accept on the tally made at collect time when
    the verdicts do not cover the lanes: only a lane that has a verdict
    counts, as the parent's `zip(ok, item_power)` had it, and verdicts
    past the lanes belong to no lane of this commit."""
    valset, power = tally_case([10] * 4, [10] * 4)
    assert power.tally == 40
    ok = ok_as([True] * verdicts) if verdicts else ok_as([])
    if ok_as is np.array:
        ok = ok.astype(np.bool_)
    if refusal is None:
        assert valset.check_commit_results(ok, power) is None
        return
    with pytest.raises(ValueError) as e:
        valset.check_commit_results(ok, power)
    assert str(e.value) == refusal


def test_a_short_verdict_vector_counts_the_lanes_it_covers_exactly():
    """Nil votes and powers past int64 among the covered lanes."""
    valset, power = tally_case([2 ** 70, 1, 1, 1], [2 ** 70, 1])
    lanes = power.for_block.tolist()
    by_power = [v.voting_power for v in valset.validators]
    for m in range(5):
        want = sum(p for p, f in zip(by_power[:m], lanes[:m]) if f)
        assert power.tally_of(m) == want and type(power.tally_of(m)) is int
    first_big = by_power.index(2 ** 70)
    ok = np.ones(first_big + 1, np.bool_)
    valset.check_commit_results(ok, power)      # the big one is covered
    with pytest.raises(ValueError, match="insufficient voting power"):
        valset.check_commit_results(ok[:first_big], power)


@pytest.mark.parametrize("verdicts", [0, 11 * 4, 11 * 4 + 1, 15 * 4 + 2])
def test_a_window_whose_resolver_returns_too_few_verdicts_is_refused(
        verdicts):
    """certify_chain hands each header `ok[lo:lo + n]`: where the
    resolver's vector ends early, the first header it does not cover in
    full is refused at its height, never accepted on the stake alone."""
    from benchmark.chain import LiteChain
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    chain = LiteChain(29, 16, 4, sign="host")
    valset, fcs = chain.decode()
    verifier = BatchVerifier("python")
    whole = verifier.verify_async
    verifier.verify_async = lambda items: (
        lambda resolve=whole(items): np.asarray(resolve())[:verdicts])
    with pytest.raises(CertificationError) as e:
        certify_chain(chain.chain_id, fcs, trusted=valset,
                      verifier=verifier, window=16)
    height = fcs[verdicts // 4].height
    covered = verdicts % 4 * 10
    assert str(e.value) == (f"height {height}: insufficient voting "
                            f"power: {covered}/40")


BIG = 2 ** 62


@pytest.mark.parametrize("powers, for_block, accepted", [
    ([10] * 6, [10] * 4, False),                # exactly 2/3 of 60
    ([10] * 5 + [11], [10] * 3 + [11], True),   # 41 of 61: 2/3 + 1
    ([3] * 18 + [1, 5], [3] * 13 + [1], False),         # 40 of 60
    ([3] * 18 + [1, 5], [3] * 12 + [5], True),          # 41 of 60
    ([10] * 6, [], False),
    ([BIG - 1] * 3, [BIG - 1] * 2, False),      # exactly 2/3, near 2^63
    ([BIG - 1, BIG - 1, BIG], [BIG - 1, BIG], True),
    ([BIG] * 6, [BIG] * 4, False),              # the total is past 2^64
    ([BIG] * 6, [BIG] * 5, True),
    ([2 ** 70, 1, 1], [2 ** 70], True),         # one power past int64
])
def test_the_stake_tally_is_exact(powers, for_block, accepted):
    valset, power = tally_case(powers, for_block)
    assert power.tally == sum(for_block) and type(power.tally) is int
    assert valset.columns().total == sum(powers)
    ok = np.ones(len(powers), np.bool_)
    if accepted:
        valset.check_commit_results(ok, power)
        return
    with pytest.raises(ValueError) as e:
        valset.check_commit_results(ok, power)
    assert str(e.value) == (f"insufficient voting power: "
                            f"{sum(for_block)}/{sum(powers)}")


# ------------------------------------------------------------ the counter --

FAMILY = "verifier_batch_sigs_total"


def batch_sigs():
    return {form: telemetry.value(FAMILY, {"form": form}) or 0
            for form in ("columns", "items")}


@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.configure(enabled=True)
    yield
    telemetry.configure(enabled=was)


def test_one_lite_window_counts_its_signatures_under_columns(
        monkeypatch, telemetry_on):
    """512 headers of 64 validators through certify_chain: one dispatch
    of 32,768 signatures as columns, four chunks. The device call alone
    is replaced (it accepts what it is given): collect, prep, the
    chunking and the check are the program's."""
    from benchmark.chain import LiteChain
    from tendermint_tpu.lite.certifier import certify_chain
    from tendermint_tpu.ops import ed25519
    chunks = []

    def enqueue(pk, rb, sb, hb, mesh=None):
        chunks.append(len(pk))
        return np.ones(len(pk), np.bool_)
    monkeypatch.setattr(ed25519, "verify_prepared_async", enqueue)
    chain = LiteChain(27, 512, 64, sign="host")
    valset, fcs = chain.decode()
    verifier = BatchVerifier("auto", mesh="off")
    before = batch_sigs()
    certify_chain(chain.chain_id, fcs, trusted=valset, verifier=verifier,
                  window=512)
    after = batch_sigs()
    assert after["columns"] - before["columns"] == 32_768
    assert after["items"] == before["items"]
    assert chunks == [8_192] * 4
    assert verifier.stats["jax_sigs"] == 32_768
    # the same window as a list of triples counts under items
    items = [(chain.pubkeys[j], chain.msgs[i], chain.sigs[i * 64 + j])
             for i in range(4) for j in range(64)]
    assert verifier.verify(items).all()
    assert batch_sigs()["items"] - before["items"] == 256
    assert batch_sigs()["columns"] == after["columns"]


PREP_FAMILY = "verifier_prep_lanes_total"


def prep_lanes():
    return {how: telemetry.value(PREP_FAMILY, {"how": how}) or 0
            for how in ("sharded", "inline")}


def random_columns(n):
    """n lanes that pass their prechecks and verify as nothing: for a
    device call that is replaced."""
    rng = np.random.default_rng(n)
    raw = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    raw[:, 63] = 0
    return SigColumns(rng.integers(0, 256, (n, 32), dtype=np.uint8),
                      [row.tobytes() for row in raw], [b"sign-bytes"],
                      np.zeros(n, np.int32))


def test_prepared_lanes_count_as_sharded_or_inline(monkeypatch):
    """A batch of two threads' worth of lanes on a host with cores to
    spare counts under `sharded`, a smaller one and any batch on a
    one-core host under `inline`, as columns or as triples; with
    telemetry off nothing is counted; a batch under auto_threshold is
    never prepared and counts under neither."""
    from tendermint_tpu.models import verifier as verifier_mod
    from tendermint_tpu.ops import ed25519
    monkeypatch.setattr(
        ed25519, "verify_prepared_async",
        lambda pk, rb, sb, hb, mesh=None: np.ones(len(pk), np.bool_))
    monkeypatch.setattr(verifier_mod, "_usable_cores", lambda: 13)
    big, small = random_columns(2 * MIN_LANES + 5), random_columns(200)
    verifier = BatchVerifier("auto", mesh="off")
    was = telemetry.enabled()
    try:
        telemetry.configure(enabled=False)
        before = prep_lanes()
        assert verifier.verify(big).all() and verifier.verify(small).all()
        assert prep_lanes() == before
        telemetry.configure(enabled=True)
        jax_sigs = telemetry.value("verifier_sigs_total",
                                   {"backend": "jax"}) or 0
        assert verifier.verify(big).all()
        assert verifier.verify(list(big)).all()
        assert verifier.verify(small).all()
        assert not verifier.verify(list(small)[:100]).any()  # the host's
        after = prep_lanes()
        assert after["sharded"] - before["sharded"] == 2 * len(big)
        assert after["inline"] - before["inline"] == len(small)
        assert telemetry.value("verifier_sigs_total", {"backend": "jax"}) \
            - jax_sigs == 2 * len(big) + len(small)
        monkeypatch.setattr(verifier_mod, "_usable_cores", lambda: 1)
        assert verifier.verify(big).all()
        assert prep_lanes()["sharded"] == after["sharded"]
        assert prep_lanes()["inline"] - after["inline"] == len(big)
    finally:
        telemetry.configure(enabled=was)


def test_a_four_vote_commit_counts_under_neither(net, telemetry_on):
    valset, commit = signed_commit(net, 4)
    verifier = BatchVerifier("auto", mesh="off")
    before = batch_sigs()
    valset.verify_commit(CHAIN, BLOCK, HEIGHT, commit,
                         verifier=verifier)
    assert batch_sigs() == before
    assert verifier.stats == {"calls": 1, "sigs": 4, "jax_sigs": 0}


def test_the_lite_windows_two_passes_are_recorded_once_each(telemetry_on):
    """`lite.headers` and `lite.votes`: one event each a window, inside
    `lite.collect`, which they fill; and a forged header is still
    refused at its own height, the first bad one in chain order."""
    from benchmark.chain import LiteChain
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    from tendermint_tpu.telemetry import trace
    chain = LiteChain(28, 24, 4, sign="host")
    valset, fcs = chain.decode()
    verifier = BatchVerifier("python")
    trace.TRACER.clear()
    certify_chain(chain.chain_id, fcs, trusted=valset, verifier=verifier,
                  window=8)
    events = [e for e in trace.TRACER.events() if e.get("ph") == "X"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert [len(by_name[k]) for k in
            ("lite.collect", "lite.headers", "lite.votes")] == [3, 3, 3]
    for collect, headers, votes in zip(by_name["lite.collect"],
                                       by_name["lite.headers"],
                                       by_name["lite.votes"]):
        assert headers["parent"] == votes["parent"] == collect["id"]
        assert headers["req"] == votes["req"] == collect["req"]
        assert headers["dur"] + votes["dur"] <= collect["dur"]
        assert headers["dur"] + votes["dur"] >= 0.9 * collect["dur"]
    # a bad vote at height 11 and a bad header at 13, in one window
    wire = list(chain.wire)
    wire[12] = chain.forged_header(13)
    _, fcs = chain.decode(wire)
    fcs[12].signed_header.header.chain_id = "another-chain"
    fcs[10].signed_header.commit.precommits[1].round = 3
    with pytest.raises(CertificationError, match="^height 11: commit vote"):
        certify_chain(chain.chain_id, fcs, trusted=valset,
                      verifier=verifier, window=8)
    _, fcs = chain.decode(wire)
    with pytest.raises(CertificationError,
                       match="^height 13: invalid signature in commit"):
        certify_chain(chain.chain_id, fcs, trusted=valset,
                      verifier=verifier, window=8)
