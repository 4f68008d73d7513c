"""Dispatch-coalescer tests: cross-call micro-batching correctness
(per-caller verdict demux, error isolation, the TM_TPU_COALESCE=off
escape hatch), the stats-race regression, and the precomputed-table
host oracle's differential against the pure RFC 8032 reference."""

import sys
import threading
import time

import numpy as np
import pytest

from tendermint_tpu.models.coalescer import DispatchCoalescer
from tendermint_tpu.models.verifier import BatchVerifier
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


# ---------------------------------------------------------------- coalescer


def test_coalescer_merges_while_dispatch_busy():
    """Deterministic merge: hold the first dispatch on a gate, pile 10
    more single-item calls into the queue, release — the second drain
    must merge all 10 into ONE dispatch and every caller must get back
    exactly its own verdict slice."""
    entered = threading.Event()
    gate = threading.Event()
    sizes = []

    def dispatch(items):
        sizes.append(len(items))
        if len(sizes) == 1:
            entered.set()
            assert gate.wait(10)
        arr = np.array([x % 2 == 0 for x in items], np.bool_)
        return lambda: arr

    c = DispatchCoalescer(dispatch, max_batch=4096, max_wait_s=0.002)
    try:
        r0 = c.submit([0])
        assert entered.wait(10)
        rs = [c.submit([i, i + 1]) for i in range(1, 21, 2)]
        gate.set()
        assert r0().tolist() == [True]
        for i, r in zip(range(1, 21, 2), rs):
            assert r().tolist() == [i % 2 == 0, (i + 1) % 2 == 0]
        assert sizes[0] == 1
        assert sizes[1] == 20, sizes  # 10 calls x 2 items, one dispatch
    finally:
        c.close()


def test_coalescer_error_isolation():
    """One caller's malformed items must surface as THAT caller's
    exception while every other merged caller still gets verdicts."""
    entered = threading.Event()
    gate = threading.Event()
    n_disp = []

    def dispatch(items):
        n_disp.append(len(items))
        if len(n_disp) == 1:
            entered.set()
            assert gate.wait(10)
        if any(not isinstance(x, int) for x in items):
            raise TypeError("bad item")
        arr = np.ones(len(items), np.bool_)
        return lambda: arr

    c = DispatchCoalescer(dispatch, max_batch=4096, max_wait_s=0.002)
    try:
        r0 = c.submit([1])
        assert entered.wait(10)
        good = [c.submit([i]) for i in range(4)]
        bad = c.submit(["poison"])
        good2 = [c.submit([i]) for i in range(4)]
        gate.set()
        assert r0().tolist() == [True]
        for r in good + good2:
            assert r().tolist() == [True]
        with pytest.raises(TypeError):
            bad()
    finally:
        c.close()


def test_coalescer_close_drains_queue():
    arrs = []

    def dispatch(items):
        arr = np.ones(len(items), np.bool_)
        arrs.append(arr)
        return lambda: arr

    c = DispatchCoalescer(dispatch, max_batch=64, max_wait_s=0.001)
    rs = [c.submit([i]) for i in range(5)]
    c.close()
    for r in rs:
        assert r().tolist() == [True]
    with pytest.raises(RuntimeError):
        c.submit([1])


# ------------------------------------------ sparse calls stay inline
#
# Only backend 'jax' queues a sub-threshold call. For 'auto' and
# 'python' the verifier asks the coalescer for an inline resolver: the
# direct path, on the thread that resolves it.


def _dispatcher_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("tm-verify-coalesce")]


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


def test_solo_caller_stays_on_its_thread_with_the_off_paths_verdicts():
    """A caller of BatchVerifier('auto') never starts the dispatcher
    thread, and its verdicts are byte for byte those of coalesce='off'
    and of the queued path, on valid, invalid and secp256k1 items."""
    before = set(_dispatcher_threads())
    batches = [[_ed_item(0)], [_ed_item(1, valid=False)], [_secp_item(0)],
               [_secp_item(1, valid=False)],
               [_ed_item(2), _ed_item(3, valid=False), _secp_item(2),
                _ed_item(4)]]
    v_off = BatchVerifier("auto", coalesce="off")
    v = BatchVerifier("auto")
    queued = DispatchCoalescer(v_off._verify_async_direct)
    try:
        for items in batches:
            want = v_off.verify(items)
            got = v.verify(items)
            merged = queued.submit(items)()
            assert got.dtype == want.dtype == merged.dtype
            assert got.tobytes() == want.tobytes() == merged.tobytes()
        assert v.verify(batches[-1]).tolist() == [True, False, True, True]
        assert v._coalescer._thread is None
        assert set(_dispatcher_threads()) - before == {queued._thread}
        assert v.stats["coalesced_calls"] == v.stats["calls"] == \
            len(batches) + 1
    finally:
        v.close()
        queued.close()


def test_a_burst_of_single_votes_goes_inline_on_its_callers_threads():
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
    try:
        for t in ths:
            t.start()
        for t in ths:
            t.join(10)
        assert {i: r for i, (_, r) in got.items()} == \
            {i: [i % 2 == 0] for i in range(8)}
        assert v._coalescer._thread is None
        assert [n for _, n in log] == [1] * 8
        assert {t for t, _ in log} == {t for t, _ in got.values()} == set(ths)
    finally:
        v.close()


@pytest.mark.parametrize("backend, queued", [("auto", False),
                                             ("python", False),
                                             ("jax", True)])
def test_the_backend_says_who_dispatches(backend, queued):
    """Where every call is the device's, 'jax', a solo call is queued
    too and four threads of them merge. Where a merged batch under the
    threshold is the host's all the same, 'auto' and 'python', nothing
    is queued however dense the callers."""
    v = BatchVerifier(backend)
    log = []
    v._verify_async_direct = _recording_dispatch(log)
    try:
        ths = [threading.Thread(
            target=lambda: [v.verify(list(range(100))) for _ in range(20)])
            for _ in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(60)
        assert v.verify([2]).tolist() == [True]
        c = v._coalescer
        assert sum(n for _, n in log) == 8001
        if queued:
            assert {t for t, _ in log} == {c._thread}
        else:
            assert c._thread is None
            assert {t for t, _ in log} == \
                set(ths) | {threading.current_thread()}
            assert len(log) == 81
    finally:
        v.close()


def test_an_inline_dispatchs_exception_is_its_callers_alone():
    log = []
    c = DispatchCoalescer(_recording_dispatch(log, poison=-1))
    start = threading.Barrier(4)
    got = {}

    def caller(i):
        start.wait(10)
        try:
            got[i] = c.inline([-1 if i == 2 else i])().tolist()
        except TypeError as e:
            got[i] = e

    ths = [threading.Thread(target=caller, args=(i,)) for i in range(4)]
    try:
        for t in ths:
            t.start()
        for t in ths:
            t.join(10)
        assert isinstance(got.pop(2), TypeError)
        assert got == {0: [True], 1: [False], 3: [False]}
        assert c._thread is None
        assert c.inline([6])().tolist() == [True]
    finally:
        c.close()


def test_an_inline_resolver_asked_twice_verifies_once():
    log = []
    c = DispatchCoalescer(_recording_dispatch(log))
    try:
        r = c.inline([1, 2])
        assert log == []            # nothing runs before it is asked
        assert r().tolist() == r().tolist() == [False, True]
        assert log == [(threading.current_thread(), 2)]
    finally:
        c.close()


def test_inline_and_queued_add_up_to_the_calls_counter():
    """`tm_verifier_coalesce_calls_total` counts every call that reached
    a coalescer, `tm_verifier_coalesce_inline_total` those that stayed
    on their caller's thread."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.models import coalescer
    assert telemetry.REGISTRY.get("verifier_coalesce_inline_total") \
        is coalescer._m_inline
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    calls0 = telemetry.value("verifier_coalesce_calls_total") or 0.0
    inline0 = telemetry.value("verifier_coalesce_inline_total") or 0.0
    c = DispatchCoalescer(_recording_dispatch([]))
    try:
        rs = [c.inline([i]) for i in range(5)]
        rs += [c.submit([i]) for i in range(3)]
        assert [r().tolist() for r in rs] == \
            [[i % 2 == 0] for i in (0, 1, 2, 3, 4, 0, 1, 2)]
        assert telemetry.value("verifier_coalesce_calls_total") \
            - calls0 == 8
        assert telemetry.value("verifier_coalesce_inline_total") \
            - inline0 == 5
    finally:
        telemetry.set_enabled(was)
        c.close()


# ------------------------------------------------- verifier + threads


def test_threaded_single_vote_callers_mixed_keys():
    """The ISSUE acceptance test: N threads submitting 1-vote batches
    with mixed ed25519/secp256k1 keys and some invalid signatures —
    every caller gets exactly its own verdicts, in order, through a
    coalescing verifier."""
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
    v = BatchVerifier("auto", coalesce="on", coalesce_wait_ms=4.0)
    try:
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
            t.join()
        assert len(results) == len(cases) * 2
        for i, (got, want) in results.items():
            assert got == [want] * 4, (i, got, want)
        assert v.stats["coalesced_calls"] == len(cases) * 2 * 4
        # merged dispatches: every submitted call accounted for exactly
        # once (calls = merged dispatch count <= submissions)
        assert 1 <= v.stats["calls"] <= v.stats["coalesced_calls"]
        assert v.stats["sigs"] == v.stats["coalesced_calls"]
    finally:
        v.close()


def test_coalesce_off_escape_hatch(monkeypatch):
    """TM_TPU_COALESCE=off restores single-call behavior: no coalescer
    is ever built, verdicts are byte-for-byte those of the direct path,
    and the env var wins over the constructor knob."""
    monkeypatch.setenv("TM_TPU_COALESCE", "off")
    v_off = BatchVerifier("auto", coalesce="on")  # env wins
    assert v_off.coalesce == "off"
    items = [_ed_item(0), _ed_item(1, valid=False), _secp_item(0)]
    out_off = v_off.verify(items)
    assert v_off._coalescer is None
    assert v_off.stats["coalesced_calls"] == 0

    monkeypatch.setenv("TM_TPU_COALESCE", "on")
    v_on = BatchVerifier("auto")
    try:
        out_on = v_on.verify(items)
        assert v_on._coalescer is not None
        assert out_off.dtype == out_on.dtype
        assert out_off.tobytes() == out_on.tobytes()
        assert out_off.tolist() == [True, False, True]
    finally:
        v_on.close()

    monkeypatch.delenv("TM_TPU_COALESCE")
    with pytest.raises(ValueError):
        BatchVerifier("auto", coalesce="sometimes")


def test_stats_thread_safety():
    """Satellite regression: stats read-modify-writes from concurrent
    reactor threads must not lose updates (they were unsynchronized
    before the stats lock)."""
    v = BatchVerifier("python", coalesce="off")
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


def test_mixed_path_stats_compensation():
    """The mixed-key re-dispatch must still count the outer call once
    (the -= compensation, now under the stats lock)."""
    v = BatchVerifier("jax", coalesce="off")
    items = [_ed_item(0), _secp_item(0), _ed_item(1)]
    out = v.verify(items)
    assert out.tolist() == [True, True, True]
    assert v.stats["calls"] == 1
    assert v.stats["sigs"] == 3


# ------------------------------------------------- async opt-in paths


def test_add_vote_async_and_verify_commit_async():
    from tendermint_tpu.types import PrivKey, Validator, ValidatorSet
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    from tendermint_tpu.types.vote_set import VoteSet

    chain = "coalesce-async"
    keys = [PrivKey.generate((i + 1).to_bytes(32, "little"))
            for i in range(4)]
    vs = ValidatorSet([Validator(k.pubkey.ed25519, 10) for k in keys])
    bid = BlockID(b"\x42" * 32, PartSetHeader(1, b"\x24" * 32))
    v = BatchVerifier("python", coalesce="on", coalesce_wait_ms=2.0)
    try:
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
    finally:
        v.close()


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


def test_coalesce_metrics_registered():
    """The tm_verifier_coalesce_* catalog passes the metrics lint."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_metrics.py")
    spec = importlib.util.spec_from_file_location("_check_metrics", path)
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    assert "tendermint_tpu.models.coalescer" in cm.INSTRUMENTED_MODULES
    assert cm.main() == 0
    from tendermint_tpu import telemetry
    for name in ("verifier_coalesce_calls_total",
                 "verifier_coalesce_dispatches_total",
                 "verifier_coalesce_batch_calls",
                 "verifier_coalesce_queue_depth",
                 "verifier_coalesce_wait_seconds",
                 "verifier_coalesce_fallback_total"):
        assert telemetry.REGISTRY.get(name) is not None, name
