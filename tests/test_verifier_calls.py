"""From a verify call to its verdicts (models/verifier.py): who verifies
a call of 1 to auto_threshold signatures and when, what `stats` counts,
the one chunk loop under each of the three host preps, the mixed-key
split, the knobs and config fields that left the boundary, a node's
choice between the process verifier and one of its own; and the
precomputed-table host oracle's differential against the pure RFC 8032
reference."""

import sys
import threading
import time

import numpy as np
import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.models import verifier as verifier_mod
from tendermint_tpu.models.verifier import BatchVerifier
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.utils import ed25519_ref as ref


def _ed_item(i: int, valid: bool = True, msg: bytes = None):
    seed = (i + 1).to_bytes(32, "little")
    m = msg if msg is not None else b"coalesce-vote-%d" % i
    sig = ref.sign(seed, m) if valid else bytes(64)
    return (ref.public_key(seed), m, sig)


def _secp_item(i: int, valid: bool = True):
    from tendermint_tpu.types.keys import Secp256k1PrivKey
    k = Secp256k1PrivKey.generate((0x5EC0 + i).to_bytes(32, "big"))
    m = b"coalesce-secp-%d" % i
    sig = k.sign(m) if valid else b"\x30\x06\x02\x01\x01\x02\x01\x01"
    return (k.pubkey.secp256k1, m, sig)


# ------------------------------ small calls stay with their caller
#
# A call of 1 to auto_threshold signatures under 'auto' or 'python' is
# verified by the thread that first asks for its verdicts; under 'jax'
# it is dispatched where it is made.


def _verify_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("tm-verify-")
            and not t.name.startswith("tm-verify-fetch")}


def _recording_dispatch(log, poison=None):
    """A stub of the verifier's direct path: logs (thread, size), and
    holds every item that is an even number to be valid."""
    def dispatch(items):
        log.append((threading.current_thread(), len(items)))
        if poison is not None and poison in items:
            raise TypeError("bad item")
        arr = np.array([x % 2 == 0 for x in items], np.bool_)
        return lambda: arr
    return dispatch


def test_solo_caller_stays_on_its_thread_with_the_direct_paths_verdicts():
    """A caller of BatchVerifier('auto') starts no thread, and its
    verdicts are byte for byte those of the direct path, on valid,
    invalid and secp256k1 items."""
    before = _verify_threads()
    batches = [[_ed_item(0)], [_ed_item(1, valid=False)], [_secp_item(0)],
               [_secp_item(1, valid=False)],
               [_ed_item(2), _ed_item(3, valid=False), _secp_item(2),
                _ed_item(4)]]
    direct = BatchVerifier("auto")
    v = BatchVerifier("auto")
    for items in batches:
        want = direct._verify_async_direct(items)()
        got = v.verify(items)
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()
    assert v.verify(batches[-1]).tolist() == [True, False, True, True]
    assert _verify_threads() == before
    assert v.stats == {"calls": len(batches) + 1, "sigs": 12, "jax_sigs": 0}


def test_a_burst_of_single_votes_is_verified_on_its_callers_threads():
    """Eight threads, one vote each, at once: four nodes of a process
    receiving the same prevotes. Nothing a device would ever see, so
    each verifies where it was received."""
    v = BatchVerifier("auto")
    log = []
    v._verify_async_direct = _recording_dispatch(log)
    start = threading.Barrier(8)
    got = {}

    def caller(i):
        start.wait(10)
        got[i] = (threading.current_thread(), v.verify([i]).tolist())

    ths = [threading.Thread(target=caller, args=(i,)) for i in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    assert {i: r for i, (_, r) in got.items()} == \
        {i: [i % 2 == 0] for i in range(8)}
    assert [n for _, n in log] == [1] * 8
    assert {t for t, _ in log} == {t for t, _ in got.values()} == set(ths)


@pytest.mark.parametrize("backend, at_the_call", [("auto", False),
                                                  ("python", False),
                                                  ("jax", True)])
def test_the_backend_says_who_dispatches(backend, at_the_call):
    """Where every call is the device's, 'jax', a small call is
    dispatched where it is made. Where it is the host's all the same,
    'auto' and 'python', it is verified by the thread that first
    resolves it, however dense the callers; no thread is started
    either way."""
    before = _verify_threads()
    v = BatchVerifier(backend)
    log = []
    v._verify_async_direct = _recording_dispatch(log)
    resolvers = [v.verify_async(list(range(100))) for _ in range(3)]
    assert len(log) == (3 if at_the_call else 0)
    got = []
    other = threading.Thread(
        target=lambda: got.extend(r().tolist() for r in resolvers))
    other.start()
    other.join(60)
    assert got == [[i % 2 == 0 for i in range(100)]] * 3
    assert [n for _, n in log] == [100] * 3
    assert {t for t, _ in log} == \
        {threading.current_thread() if at_the_call else other}

    ths = [threading.Thread(
        target=lambda: [v.verify(list(range(100))) for _ in range(20)])
        for _ in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert len(log) == 83 and sum(n for _, n in log) == 8300
    assert {t for t, _ in log[3:]} == set(ths)
    assert _verify_threads() == before


def test_a_small_calls_exception_is_its_callers_alone():
    v = BatchVerifier("auto")
    v._verify_async_direct = _recording_dispatch([], poison=-1)
    start = threading.Barrier(4)
    got = {}

    def caller(i):
        start.wait(10)
        try:
            got[i] = v.verify([-1 if i == 2 else i]).tolist()
        except TypeError as e:
            got[i] = e

    ths = [threading.Thread(target=caller, args=(i,)) for i in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    assert isinstance(got.pop(2), TypeError)
    assert got == {0: [True], 1: [False], 3: [False]}
    assert v.verify([6]).tolist() == [True]


def test_a_resolver_asked_twice_verifies_once():
    v = BatchVerifier("python")
    log = []
    v._verify_async_direct = _recording_dispatch(log)
    items = [1, 2]
    r = v.verify_async(items)
    items.append(3)             # the call keeps the items it was given
    assert log == []            # nothing runs before it is asked
    assert r().tolist() == r().tolist() == [False, True]
    assert log == [(threading.current_thread(), 2)]


@pytest.mark.parametrize("backend, stats", [
    ("auto", {"calls": 4, "sigs": 12, "jax_sigs": 0}),
    ("python", {"calls": 4, "sigs": 12, "jax_sigs": 0}),
    ("jax", {"calls": 5, "sigs": 13, "jax_sigs": 12})])
def test_stats_count_the_same_calls_as_before(backend, stats):
    """`calls`, `sigs` and `jax_sigs` after one fixed run of calls: an
    empty one, a vote, a small commit, a mixed batch (its secp256k1
    lane is the host's under every backend) and a vote dispatched and
    never resolved, which only 'jax' has verified. The numbers are
    those the verifier read with the coalescer's queue in front of it
    (PR 44's tree, the same calls)."""
    v = BatchVerifier(backend, mesh="off")
    assert v.verify([]).tolist() == []
    assert v.verify([_ed_item(0)]).tolist() == [True]
    commit = [_ed_item(i, valid=i != 2) for i in range(8)]
    assert v.verify(commit).tolist() == [i != 2 for i in range(8)]
    assert v.verify([_ed_item(0), _secp_item(0), _ed_item(1)]).all()
    v.verify_async([_ed_item(3)])
    assert v.stats == stats


# ------------------------------------------------- verifier + threads


def test_threaded_single_vote_callers_mixed_keys():
    """The ISSUE acceptance test: N threads submitting 1-vote batches
    with mixed ed25519/secp256k1 keys and some invalid signatures —
    every caller gets exactly its own verdicts, in order, and every
    call is counted once."""
    cases = [
        (_ed_item(0), True),
        (_ed_item(1, valid=False), False),
        (_secp_item(0), True),
        (_ed_item(2), True),
        (_secp_item(1, valid=False), False),
        (_ed_item(3, msg=b"other", valid=True), True),
        (_ed_item(4, valid=False), False),
        (_ed_item(5), True),
    ]
    v = BatchVerifier("auto")
    results = {}

    def worker(i):
        item, want = cases[i % len(cases)]
        got = []
        for _ in range(4):
            got.append(bool(v.verify([item])[0]))
        results[i] = (got, want)

    ths = [threading.Thread(target=worker, args=(i,))
           for i in range(len(cases) * 2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert len(results) == len(cases) * 2
    for i, (got, want) in results.items():
        assert got == [want] * 4, (i, got, want)
    assert v.stats == {"calls": len(cases) * 2 * 4,
                       "sigs": len(cases) * 2 * 4, "jax_sigs": 0}


def test_stats_thread_safety():
    """Satellite regression: stats read-modify-writes from concurrent
    reactor threads must not lose updates (they were unsynchronized
    before the stats lock)."""
    v = BatchVerifier("python")
    n_threads, n_iter = 8, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # force frequent preemption
    try:
        def worker():
            for _ in range(n_iter):
                v.verify([])

        ths = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert v.stats["calls"] == n_threads * n_iter


@pytest.mark.parametrize("backend, threshold", [("jax", None), ("auto", 1)])
def test_mixed_path_stats_compensation(backend, threshold):
    """A batch the native prep declines for its secp256k1 lane: the
    re-dispatch of the ed25519 lanes must still count the outer call
    once (the -= compensation, under the stats lock), and only they
    are the device's."""
    v = BatchVerifier(backend, auto_threshold=threshold, mesh="off")
    items = [_ed_item(0), _secp_item(0), _ed_item(1, valid=False),
             _secp_item(1, valid=False)]
    out = v.verify(items)
    assert out.tolist() == [True, True, False, False]
    assert v.stats == {"calls": 1, "sigs": 4, "jax_sigs": 2}
    all_secp = [_secp_item(0), _secp_item(1, valid=False), _secp_item(2)]
    assert v.verify(all_secp).tolist() == [True, False, True]
    assert v.stats == {"calls": 2, "sigs": 7, "jax_sigs": 2}


# ------------------------------------------------- async opt-in paths


def test_add_vote_async_and_verify_commit_async():
    from tendermint_tpu.types import PrivKey, Validator, ValidatorSet
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    from tendermint_tpu.types.vote_set import VoteSet

    chain = "async-calls"
    keys = [PrivKey.generate((i + 1).to_bytes(32, "little"))
            for i in range(4)]
    vs = ValidatorSet([Validator(k.pubkey.ed25519, 10) for k in keys])
    bid = BlockID(b"\x42" * 32, PartSetHeader(1, b"\x24" * 32))
    v = BatchVerifier("python")
    vset = VoteSet(chain, 1, 0, VoteType.PRECOMMIT, vs, verifier=v)
    resolvers = []
    for idx, val in enumerate(vs.validators):
        key = next(k for k in keys
                   if k.pubkey.ed25519 == val.pubkey)
        vote = Vote(val.address, idx, 1, 0, 1000 + idx,
                    VoteType.PRECOMMIT, bid)
        vote.signature = key.sign(vote.sign_bytes(chain))
        resolvers.append(vset.add_vote_async(vote))
    # crypto dispatched for all four; apply on the owning thread
    assert all(r() for r in resolvers)
    assert vset.has_two_thirds_majority()
    commit = vset.make_commit()

    finish = vs.verify_commit_async(chain, bid, 1, commit, verifier=v)
    finish()  # no raise: valid commit
    commit.precommits[0].signature = bytes(64)
    bad = vs.verify_commit_async(chain, bid, 1, commit, verifier=v)
    with pytest.raises(ValueError):
        bad()
    # invalid-signature votes fail at the resolver, like add_vote
    vset2 = VoteSet(chain, 1, 0, VoteType.PREVOTE, vs, verifier=v)
    vote = Vote(vs.validators[0].address, 0, 1, 0, 1, VoteType.PREVOTE,
                bid)
    vote.signature = bytes(64)
    r = vset2.add_vote_async(vote)
    with pytest.raises(ValueError, match="invalid signature"):
        r()


# --------------------------------------------------- the one chunk loop
#
# Whoever prepared the arrays (native.prep_columns, native.prep_items,
# or ops/ed25519.prepare_batch_bytes for a batch the native prep
# declines), one loop cuts them at BATCH_CHUNK, here patched to 16.

CHUNK = 16


@pytest.fixture
def traced():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.TRACER.clear()
    yield
    telemetry.set_enabled(was)


@pytest.mark.parametrize("n, bounds", [(CHUNK, [(0, 16)]),
                                       (CHUNK + 5, [(0, 16), (16, 21)])])
@pytest.mark.parametrize("prepared_by", ["prep_columns", "prep_items",
                                         "prepare_batch_bytes"])
def test_the_one_chunk_loop(prepared_by, n, bounds, monkeypatch, traced):
    """The RFC 8032 oracle's verdicts with one bad lane a chunk, the
    same (lo, hi) bounds and padded shapes, one `verify.prep`, one
    `verify.enqueue` a chunk and one `verify.fetch`, under each of the
    three preps; `form` says columns only for columns."""
    from tendermint_tpu import native
    from tendermint_tpu.ops import ed25519
    monkeypatch.setattr(verifier_mod, "BATCH_CHUNK", CHUNK)
    bad = {lo + 3 for lo, _ in bounds}
    items = [_ed_item(i, msg=b"lane-%d" % (i // 4)) for i in range(n)]
    for i in bad:
        pk, msg, sig = items[i]
        items[i] = (pk, msg, sig[:9] + bytes([sig[9] ^ 2]) + sig[10:])
    oracle = [ref.verify(*it) for it in items]
    assert oracle == [i not in bad for i in range(n)]
    batch = items
    if prepared_by == "prep_columns":
        msgs = sorted({it[1] for it in items})
        batch = SigColumns(
            np.frombuffer(b"".join(it[0] for it in items),
                          np.uint8).reshape(n, 32),
            [it[2] for it in items], msgs,
            np.array([msgs.index(it[1]) for it in items], np.int32))
    elif prepared_by == "prepare_batch_bytes":
        # a member that is no `bytes`: the native prep declines
        batch = [(pk, bytearray(msg), sig) for pk, msg, sig in items]

    took = []
    for name in ("prep_columns", "prep_items"):
        def tap(*args, _inner=getattr(native, name), _name=name):
            out = _inner(*args)
            if out is not None:
                took.append(_name)
            return out
        monkeypatch.setattr(native, name, tap)
    inner_prepare = ed25519.prepare_batch_bytes

    def prepare(*args):
        took.append("prepare_batch_bytes")
        return inner_prepare(*args)
    monkeypatch.setattr(ed25519, "prepare_batch_bytes", prepare)
    chunks = []
    inner_async = ed25519.verify_prepared_async

    def prepared_async(pk, rb, sb, hb, mesh=None):
        assert pk.shape == rb.shape == sb.shape == hb.shape
        chunks.append(pk.shape[0])
        return inner_async(pk, rb, sb, hb, mesh=mesh)
    monkeypatch.setattr(ed25519, "verify_prepared_async", prepared_async)

    def batch_sigs():
        return {k[0]: c.value
                for k, c in verifier_mod._m_batch_sigs.children()}
    form = batch_sigs()

    v = BatchVerifier("jax", mesh="off")
    assert v.verify(batch).tolist() == oracle
    assert took == [prepared_by]
    assert chunks == [hi - lo for lo, hi in bounds]
    assert v.stats == {"calls": 1, "sigs": n, "jax_sigs": n}
    by = {}
    for e in telemetry.TRACER.events():
        if e["name"].startswith("verify."):
            by.setdefault(e["name"], []).append(e)
    assert set(by) - {"verify.predecomp"} == {
        "verify.dispatch", "verify.prep", "verify.enqueue", "verify.fetch",
        "verify.inflight"}
    (prep,) = by["verify.prep"]
    assert prep["args"]["n"] == n
    assert [e["args"]["rows"] for e in by["verify.enqueue"]] == \
        [ed25519._bucket(hi - lo) for lo, hi in bounds]
    (fetch,) = by["verify.fetch"]
    assert fetch["args"]["chunks"] == len(bounds)
    assert fetch["cause"] == by["verify.dispatch"][0]["id"]
    after = batch_sigs()
    want_form = "columns" if prepared_by == "prep_columns" else "items"
    assert {k: after[k] - form.get(k, 0) for k in after
            if after[k] != form.get(k, 0)} == {want_form: n}


# ------------------------------- what left the boundary stays outside

# spelled in halves: the tree is grepped for the whole names
GONE = tuple("TM_TPU_" + tail for tail in (
    "COALESCE", "COALESCE_WAIT_MS", "COALESCE_MAX_BATCH", "NO_PALLAS",
    "FETCH_WORKERS"))


def test_the_five_knobs_are_in_no_catalog():
    import os

    from tendermint_tpu.utils import knobs
    names = [k.name for k in knobs.CATALOG]
    assert len(names) == len(set(names)) == 38
    assert not set(GONE) & set(names)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "knobs.md")) as f:
        doc = f.read()
    assert not [g for g in GONE if g in doc]
    assert sum(line.startswith("| `TM_TPU_") for line in
               doc.splitlines()) == 38
    assert verifier_mod.FETCH_WORKERS == 8


def test_a_config_file_that_still_names_the_queue_is_read_as_before(
        tmp_path):
    """`verifier_coalesce` is now a field the loader does not know: it
    is passed over like any other, and its neighbours are read."""
    import json

    from tendermint_tpu.config import default_config
    (tmp_path / "config").mkdir()
    (tmp_path / "config" / "config.json").write_text(json.dumps(
        {"base": {"verifier_coalesce": "off",
                  "verifier_coalesce_wait_ms": 9.0,
                  "verifier_backend": "python"}}))
    cfg = default_config(str(tmp_path))
    assert cfg.base.verifier_backend == "python"
    assert cfg.base.verifier_mesh == "auto"
    assert not [f for f in vars(cfg.base) if "coalesce" in f]


@pytest.mark.parametrize("backend, shared", [("auto", True),
                                             ("python", False)])
def test_a_node_shares_the_process_verifier_unless_its_config_differs(
        backend, shared):
    from tendermint_tpu.config import test_config as make_test_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)
    before = _verify_threads()
    key = PrivKey.generate(b"\x2d" * 32)
    gen = GenesisDoc(chain_id="verifier-of-a-node", genesis_time_ns=1,
                     validators=[GenesisValidator(key.pubkey.ed25519, 10)])
    cfg = make_test_config("")
    cfg.base.verifier_backend = backend
    node = Node(cfg, gen, priv_validator=PrivValidator(LocalSigner(key)),
                in_memory=True)
    assert (node.verifier is verifier_mod.default_verifier()) is shared
    assert node.verifier.backend == backend
    assert node.consensus.block_exec.verifier is node.verifier
    calls = node.verifier.stats["calls"]
    node.start()
    try:
        deadline = time.monotonic() + 30
        while node.height < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert node.height >= 2
    finally:
        node.stop()
    assert node.verifier.stats["calls"] > calls
    assert node.verifier.verify([_ed_item(0)]).tolist() == [True]
    assert _verify_threads() == before


# ------------------------------------------- precomputed-table oracle


def test_fast_verify_matches_oracle():
    """utils/ed25519_fast must be verdict-identical to the pure RFC 8032
    oracle on valid, tampered, non-canonical and garbage inputs — a
    split here is a consensus fork on the no-OpenSSL host path."""
    import random

    from tendermint_tpu.utils import ed25519_fast as fast

    rng = random.Random(20260804)
    p255 = (1 << 255) - 19
    fast.cache_clear()
    for i in range(8):
        seed = rng.randbytes(32)
        pk = ref.public_key(seed)
        msg = rng.randbytes(rng.randrange(0, 64))
        sig = ref.sign(seed, msg)
        high_s = sig[:32] + (
            (int.from_bytes(sig[32:], "little") + ref.L) %
            (1 << 256)).to_bytes(32, "little")
        cases = [
            (pk, msg, sig),                                  # valid
            (pk, msg + b"x", sig),                           # wrong msg
            (pk, msg, sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]),
            (pk, msg, sig[:-1]),                             # short sig
            (pk, msg, rng.randbytes(64)),                    # garbage
            (rng.randbytes(32), msg, sig),                   # wrong key
            (pk, msg, high_s),                               # s >= L
            (pk[:-1], msg, sig),                             # short key
        ]
        for p, m, s in cases:
            assert fast.verify(p, m, s) == ref.verify(p, m, s), \
                (i, p.hex(), s.hex())
    # adversarial non-canonical encodings (the OpenSSL leniency gap set)
    msg = b"adversarial"
    ncid = (1).to_bytes(32, "little")
    ncid = ncid[:31] + bytes([ncid[31] | 0x80])       # y=1, sign=1
    ncid2 = (p255 - 1).to_bytes(32, "little")
    ncid2 = ncid2[:31] + bytes([ncid2[31] | 0x80])    # y=-1, sign=1
    ybig = (p255 + 2).to_bytes(32, "little")          # y >= p
    seed = b"\x07" * 32
    for bad in (ncid, ncid2, ybig):
        for pkey, sg in ((bad, bad + bytes(32)),
                         (ref.public_key(seed), bad + bytes(32)),
                         (bad, ref.sign(seed, msg))):
            assert fast.verify(pkey, msg, sg) == ref.verify(pkey, msg, sg)
    # repeat hits (cached tables) keep identical verdicts
    pk = ref.public_key(seed)
    sig = ref.sign(seed, msg)
    for _ in range(3):
        assert fast.verify(pk, msg, sig)
        assert not fast.verify(pk, msg + b"!", sig)


def test_verify_many_matches_verify_any():
    from tendermint_tpu.types.keys import verify_any, verify_many

    items = [_ed_item(0), _ed_item(1, valid=False), _secp_item(0),
             _ed_item(2), _ed_item(3), (b"\x00" * 7, b"m", b"s"),
             _secp_item(1, valid=False)]
    got = verify_many(items)
    assert got == [verify_any(*it) for it in items]
    assert got == [True, False, True, True, True, False, False]
    # below the table threshold: still exact
    small = items[:2]
    assert verify_many(small) == [verify_any(*it) for it in small]
