"""Native C++ host-ops: differential tests against the pure-Python spec
implementation and hashlib (ops/merkle.py's host reference)."""

import hashlib
import os
import struct

import pytest

from tendermint_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain for native hostops")


def py_leaf(item):
    return hashlib.sha256(b"\x00" + item).digest()


def py_node(l, r):
    return hashlib.sha256(b"\x01" + l + r).digest()


def py_final(n, tr):
    return hashlib.sha256(b"\x02" + struct.pack("<Q", n) + tr).digest()


def py_root(items):
    n = len(items)
    if n == 0:
        return py_final(0, b"\x00" * 32)
    m = 1
    while m < n:
        m *= 2
    level = [py_leaf(it) for it in items] + [b"\x00" * 32] * (m - n)
    while len(level) > 1:
        level = [py_node(level[i], level[i + 1])
                 for i in range(0, len(level), 2)]
    return py_final(n, level[0])


def test_sha256_batch_matches_hashlib():
    items = [b"", b"a", b"ab" * 100, os.urandom(1000), b"\x00" * 64,
             os.urandom(63), os.urandom(65)]
    got = native.sha256_batch(items)
    want = [hashlib.sha256(it).digest() for it in items]
    assert got == want


@pytest.mark.parametrize("n", [0, 1, 2, 3, 4, 5, 7, 8, 9, 100, 257])
def test_merkle_root_matches_spec(n):
    items = [b"item-%d" % i for i in range(n)]
    assert native.merkle_root(items) == py_root(items)


def test_merkle_root_from_digests():
    digests = [hashlib.sha256(b"%d" % i).digest() for i in range(37)]
    m = 1
    while m < 37:
        m *= 2
    level = list(digests) + [b"\x00" * 32] * (m - 37)
    while len(level) > 1:
        level = [py_node(level[i], level[i + 1])
                 for i in range(0, len(level), 2)]
    assert native.merkle_root_from_digests(digests) == py_final(37, level[0])


@pytest.mark.parametrize("n,idx", [(1, 0), (5, 0), (5, 4), (8, 3),
                                   (100, 77)])
def test_merkle_proof_verifies(n, idx):
    from tendermint_tpu.ops import merkle
    items = [b"p-%d" % i for i in range(n)]
    root, aunts = native.merkle_proof(items, idx)
    assert root == py_root(items)
    assert merkle.verify_proof_host(root, n, idx, items[idx], aunts)
    # tampered item fails
    assert not merkle.verify_proof_host(root, n, idx, b"evil", aunts)


def test_merkle_host_functions_use_native_consistently():
    """ops/merkle host entry points agree with the pure spec regardless of
    which path (native or hashlib) served them."""
    from tendermint_tpu.ops import merkle
    items = [os.urandom(50) for _ in range(23)]
    assert merkle.root_host(items) == py_root(items)
    root, aunts = merkle.proof_host(items, 11)
    assert root == py_root(items)
    assert merkle.verify_proof_host(root, 23, 11, items[11], aunts)


def test_native_speedup_on_large_tree():
    """The point of the C++ path: whole-tree builds beat per-node hashlib
    loops. Soft-asserted (>=2x) to avoid CI flakiness."""
    import time
    from tendermint_tpu.ops import merkle

    items = [os.urandom(100) for _ in range(4096)]
    t0 = time.perf_counter()
    native_root = native.merkle_root(items)
    t_native = time.perf_counter() - t0

    t0 = time.perf_counter()
    py = merkle.root_from_digests_host.__wrapped__ \
        if hasattr(merkle.root_from_digests_host, "__wrapped__") else None
    want = py_root(items)
    t_py = time.perf_counter() - t0

    assert native_root == want
    assert t_native < t_py, (t_native, t_py)


def test_codec_differential_vs_pure():
    """native/codec.cpp canonical_dumps must be byte-equal to the pure
    _canon+json.dumps specification path on randomized object trees,
    raise TypeError on floats, and Fallback (-> pure path) on non-str
    dict keys."""
    import random
    import string

    import pytest

    from tendermint_tpu import native
    from tendermint_tpu.types import encoding

    mod = native.codec()
    if mod is None:
        pytest.skip("native codec unavailable")

    rng = random.Random(1234)

    def rand_obj(depth=0):
        r = rng.random()
        if depth > 4 or r < 0.25:
            return rng.choice([
                None, True, False,
                rng.randrange(-2 ** 70, 2 ** 70),
                rng.randrange(-1000, 1000),
                ''.join(rng.choice(string.printable)
                        for _ in range(rng.randrange(0, 30))),
                'unicode: ñ→🎉 \x01\x1f "quoted" back\\slash',
                rng.randbytes(rng.randrange(0, 40)),
                bytearray(rng.randbytes(5)),
            ])
        if r < 0.55:
            return {''.join(rng.choice(string.ascii_letters + 'é\n"\\')
                            for _ in range(rng.randrange(1, 10))):
                    rand_obj(depth + 1)
                    for _ in range(rng.randrange(0, 8))}
        return [rand_obj(depth + 1) for _ in range(rng.randrange(0, 8))]

    for _ in range(1500):
        o = rand_obj()
        assert mod.canonical_dumps(o) == encoding._pure_cdumps(o), o

    class Wrapped:
        def to_obj(self):
            return {"x": b"\x01\x02", "n": [1, None]}

    assert mod.canonical_dumps(Wrapped()) == \
        encoding._pure_cdumps(Wrapped())

    with pytest.raises(TypeError):
        mod.canonical_dumps({"a": 1.5})
    with pytest.raises(mod.Fallback):
        mod.canonical_dumps({1: "a"})
    # cdumps itself falls back and matches pure for non-str keys
    assert encoding.cdumps({1: "a"}) == encoding._pure_cdumps({1: "a"})


def test_prep_items_differential_vs_python():
    """native.prep_items must byte-match prepare_batch_bytes (the
    Python/ctypes path) across valid, malformed, and boundary inputs,
    and return None for shapes routed to the general path."""
    import random

    import numpy as np

    from tendermint_tpu.ops import ed25519
    from tendermint_tpu.utils import ed25519_ref as ref

    if native._prep() is None:
        pytest.skip("prep extension unavailable")

    rng = random.Random(7)
    items = []
    for i in range(64):
        seed = (i + 1).to_bytes(32, "little")
        pk = ref.public_key(seed)
        m = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 400)))
        items.append((pk, m, ref.sign(seed, m)))
    items[5] = (items[5][0][:31], items[5][1], items[5][2])      # short pk
    items[7] = (items[7][0], items[7][1], items[7][2][:63])      # short sig
    items[9] = (items[9][0], items[9][1],
                items[9][2][:32] + ed25519.L_ORDER.to_bytes(32, "little"))
    items[11] = (items[11][0], b"\x55" * 700, items[11][2])      # long msg
    items[13] = (b"\x00" * 32, items[13][1], items[13][2])       # non-point

    out = native.prep_items(items)
    assert out is not None
    pk, rb, sb, hb, pre = out
    ref_out = ed25519.prepare_batch_bytes(
        [i[0] for i in items], [i[1] for i in items],
        [i[2] for i in items])
    for got, want in zip((pk, rb, sb, hb, pre), ref_out):
        assert np.array_equal(got, want)
    assert not pre[5] and not pre[7] and not pre[9] and pre[13]

    # shapes the fast path must hand back to the general path
    assert native.prep_items(
        [(b"\x02" + b"\x01" * 32, b"m", b"s" * 64)]) is None  # secp256k1
    assert native.prep_items(
        [(bytearray(32), b"m", b"s" * 64)]) is None           # non-bytes
    assert native.prep_items([(b"a" * 32, b"m")]) is None     # 2-tuple
    empty = native.prep_items([])
    assert empty is not None and empty[4].shape == (0,)


# ----------------------------------- the prep's hashing on several threads --

L_ORDER = 2 ** 252 + 27742317777372353535851937790883648493
PREP_SIZES = (0, 1, 1_023, 2_047, 2_048, 2_049, 10_000)
PREP_THREADS = (1, 2, 3, 8)


def shard_edges(n):
    """Both lanes at every boundary between shards of [0, n) for each
    thread count the tests use, and the batch's two ends."""
    edges = {0, n - 1}
    for threads in PREP_THREADS:
        for t in range(1, threads):
            edges.update((n * t // threads - 1, n * t // threads))
    return sorted(i for i in edges if 0 <= i < n)


_prep_batches = {}


def prep_batch(n, form, messages):
    """n lanes of random keys and signatures with s < L, a 118-byte
    message each (`distinct`) or one per 64 lanes (`shared`), and at the
    shards' edges lanes that fail each precheck in turn. -> (the call's
    arguments in `form`, the five arrays by a plain reference)."""
    key = (n, form, messages)
    if key in _prep_batches:
        return _prep_batches[key]
    import random

    import numpy as np
    rng = random.Random(n)
    lanes_a_msg = 1 if messages == "distinct" else 64
    msgs = [rng.randbytes(118) for _ in range(-(-n // lanes_a_msg) or 1)]
    idx = [i // lanes_a_msg for i in range(n)]
    pks = [rng.randbytes(32) for _ in range(n)]
    sigs = [rng.randbytes(63) + b"\x00" for _ in range(n)]
    # columns hold keys as an n x 32 array: only a triple's can be short
    faults = ("short_sig", "s_ge_L", "long_sig") + (
        ("short_key",) if form == "items" else ())
    for k, i in enumerate(shard_edges(n) if n >= 16 else ()):
        fault = faults[k % len(faults)]
        if fault == "short_sig":
            sigs[i] = sigs[i][:63]
        elif fault == "long_sig":
            sigs[i] = sigs[i] + b"\x00"
        elif fault == "s_ge_L":
            sigs[i] = sigs[i][:32] + L_ORDER.to_bytes(32, "little")
        else:
            pks[i] = pks[i][:31]
    want = [bytearray(32 * n) for _ in range(4)] + [bytearray(n)]
    for i in range(n):
        pk, sig, m = pks[i], sigs[i], msgs[idx[i]]
        if len(pk) != 32 or len(sig) != 64 or \
                int.from_bytes(sig[32:], "little") >= L_ORDER:
            continue
        h = int.from_bytes(hashlib.sha512(sig[:32] + pk + m).digest(),
                           "little") % L_ORDER
        for row, val in zip(want, (pk, sig[:32], sig[32:],
                                   h.to_bytes(32, "little"))):
            row[32 * i:32 * i + 32] = val
        want[4][i] = 1
    if form == "items":
        args = ([(pks[i], msgs[idx[i]], sigs[i]) for i in range(n)],)
    else:
        args = (np.frombuffer(b"".join(pks), np.uint8).reshape(n, 32),
                sigs, msgs, np.array(idx, np.int32))
    _prep_batches[key] = args, [bytes(w) for w in want]
    return _prep_batches[key]


def prep_call(form):
    return native.prep_items if form == "items" else native.prep_columns


@pytest.mark.parametrize("messages", ["distinct", "shared"])
@pytest.mark.parametrize("n", PREP_SIZES)
@pytest.mark.parametrize("threads", PREP_THREADS)
@pytest.mark.parametrize("form", ["items", "columns"])
def test_prep_on_several_threads_is_the_prep_on_one(form, threads, n,
                                                    messages):
    """Whatever the thread count and wherever the shards' edges fall,
    the five arrays are one thread's byte for byte, and what a plain
    reference gives: hashlib's SHA-512(R || A || M) as an integer mod L
    for a lane that passes the three length checks and s < L, zeros and
    pre = 0 for one that does not."""
    if native._prep() is None:
        pytest.skip("prep extension unavailable")
    args, want = prep_batch(n, form, messages)
    got = prep_call(form)(*args, threads)
    one = prep_call(form)(*args, 1)
    assert got is not None and one is not None
    for g, o in zip(got, one):
        assert g.dtype == o.dtype and g.shape == o.shape
        assert g.tobytes() == o.tobytes()
    for g, w in zip(got[:4], want):
        assert g.tobytes() == w
    assert got[4].tolist() == [bool(b) for b in want[4]]
    if n >= 16:
        assert not got[4][shard_edges(n)].any()
        assert got[4].sum() == n - len(shard_edges(n))


@pytest.mark.parametrize("form", ["items", "columns"])
def test_prep_on_several_threads_still_hands_back_what_it_does_not_cover(
        form):
    """A member that is no bytes object, in the last shard's last lane:
    None, as on one thread, for the general path to take."""
    if native._prep() is None:
        pytest.skip("prep extension unavailable")
    args, _ = prep_batch(2_049, form, "shared")
    if form == "items":
        items = list(args[0])
        items[-1] = (items[-1][0], items[-1][1], bytearray(items[-1][2]))
        args = (items,)
    else:
        sigs = list(args[1])
        sigs[-1] = bytearray(sigs[-1])
        args = (args[0], sigs, args[2], args[3])
    for threads in (1, 2, 8):
        assert prep_call(form)(*args, threads) is None


@pytest.mark.parametrize("form", ["items", "columns"])
def test_no_thread_of_a_prep_outlives_its_call(form):
    """The hashing threads are joined inside the call: the process has
    as many tasks after it as before, so it forks as safely."""
    if native._prep() is None:
        pytest.skip("prep extension unavailable")
    args, want = prep_batch(10_000, form, "distinct")
    tasks = lambda: len(os.listdir("/proc/self/task"))
    before = tasks()
    for threads in (8, 3, 8):
        got = prep_call(form)(*args, threads)
        assert got[3].tobytes() == want[3]
        assert tasks() == before


def test_kvcore_differential_vs_python_app():
    """Native KV core vs the pure-Python KVStoreApp: identical app
    hashes, store contents, and results hashes across mixed batches,
    key overwrites, and val: txs (which route to the Python path)."""
    import random

    from tendermint_tpu.abci.apps.kvstore import KVStoreApp
    from tendermint_tpu.state.execution import results_hash

    if native.kv() is None:
        pytest.skip("kv extension unavailable")

    pure = KVStoreApp(use_native=False)
    assert pure._core is None
    nat = KVStoreApp()
    assert nat._core is not None

    rng = random.Random(13)
    for block in range(6):
        txs = []
        for i in range(200):
            k = b"k%d" % rng.randrange(150)   # frequent overwrites
            v = bytes(rng.randrange(256) for _ in range(rng.randrange(20)))
            txs.append(k + b"=" + v if rng.random() < 0.8 else k)
        if block == 3:
            txs.insert(7, b"val:" + b"aa" * 32 + b"/5")  # python fallback
        r_nat = nat.deliver_tx_batch(txs)
        r_pure = [pure.deliver_tx(tx) for tx in txs]
        assert results_hash(r_nat) == results_hash(r_pure)
        assert [r.to_obj() for r in r_nat] == [r.to_obj() for r in r_pure]
        assert nat.commit() == pure.commit(), f"block {block}"
    assert dict(nat.store.items()) == pure.store
    assert len(nat.store) == len(pure.store)
    assert nat.store.get(b"k1") == pure.store.get(b"k1")
    assert nat.tx_count == pure.tx_count
