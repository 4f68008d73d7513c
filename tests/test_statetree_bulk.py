"""The state tree's bulk build, the genesis' choice of commit backend,
the records file a chain starts with, and the spans and the counter
PR 35 added to the tree and the read path."""

import hashlib
import random
import time

import pytest

from tendermint_tpu import statetree, telemetry
from tendermint_tpu.abci.apps import records
from tendermint_tpu.abci.apps.kvstore import KVStoreApp
from tendermint_tpu.abci.types import ValidatorUpdate
from tendermint_tpu.statetree import StateTree
from tendermint_tpu.statetree.store import Leaf
from tendermint_tpu.telemetry import trace

VALS = [ValidatorUpdate(b"\x01" * 32, 10)]


def same_tree(a, b) -> bool:
    """Node for node: kinds, split bits, keys, values and hashes."""
    stack = [(a, b)]
    while stack:
        x, y = stack.pop()
        if type(x) is not type(y) or x.hash != y.hash or x.hash is None:
            return False
        if isinstance(x, Leaf):
            if (x.kh, x.key, x.value) != (y.kh, y.key, y.value):
                return False
        else:
            if x.bit != y.bit:
                return False
            stack += [(x.left, y.left), (x.right, y.right)]
    return True


def pairs_of(seed: int, n: int, value_bytes: int = 40):
    rng = random.Random(seed)
    out = [(b"user%019d" % rng.randrange(10 ** 19),
            rng.randbytes(rng.randrange(0, value_bytes))) for _ in range(n)]
    return out + out[:n // 3]       # some keys twice: the last value stays


@pytest.mark.parametrize("n", [0, 1, 2, 3, 17, 600, 5000])
def test_bulk_build_equals_set_and_commit_bit_for_bit(n):
    pairs = pairs_of(n, n)
    one = StateTree()
    for k, v in pairs:
        one.set(k, v)
    bulk = StateTree()
    assert bulk.load(iter(pairs)) == len(one) == len(bulk)
    assert bulk.commit(4) == one.commit(4)
    assert n == 0 or same_tree(one._root, bulk._root)
    # and it goes on as the other does
    for t in (one, bulk):
        t.set(b"later", b"x")
        t.set(pairs[0][0] if pairs else b"k", b"y")
    assert bulk.commit(5) == one.commit(5)
    assert same_tree(one._root, bulk._root)


def test_bulk_build_needs_an_empty_tree_and_hashes_everything():
    t = StateTree()
    t.set(b"k", b"v")
    with pytest.raises(ValueError):
        t.load([(b"a", b"b")])
    t = StateTree()
    t.load(pairs_of(3, 50))
    assert t._root.hash is not None and not t._fresh


@pytest.mark.parametrize("backend", ["tree", "buckets"])
def test_restore_items_and_the_genesis_load_share_one_loader(
        backend, tmp_path, monkeypatch):
    monkeypatch.delenv("TM_TPU_STATE_TREE", raising=False)
    pairs = pairs_of(11, 300)
    entry = records.write_records(str(tmp_path / "r.bin"), pairs)
    state = {"kvstore": {"commit_backend": backend, "records": entry}}
    loaded = KVStoreApp()
    loaded.init_chain(VALS, "c", state)
    assert loaded.height == 0 and len(loaded.store) == len(dict(pairs))
    restored = KVStoreApp()
    restored.init_chain(VALS, "c", {"kvstore": {"commit_backend": backend}})
    assert restored.restore_items(pairs, 0) == loaded.app_hash
    # block 1 on top of the loaded store: the same hash on both
    for app in (loaded, restored):
        app.deliver_tx(b"fresh=1")
        app.commit()
    assert loaded.app_hash == restored.app_hash and loaded.height == 1
    if backend == "tree":
        assert same_tree(loaded._tree._root, restored._tree._root)
        # version 0 is the loaded store, and provable
        value, proof = loaded._tree.prove(pairs[-1][0], 0)
        assert value == dict(pairs)[pairs[-1][0]]
        statetree.verify(proof, pairs[-1][0], value,
                         loaded._tree.app_hash_at(0))


def test_the_genesis_chooses_the_backend_over_the_environment(monkeypatch):
    txs = [b"k%d=v%d" % (i, i) for i in range(40)]

    def run(app_state, env):
        if env is None:
            monkeypatch.delenv("TM_TPU_STATE_TREE", raising=False)
        else:
            monkeypatch.setenv("TM_TPU_STATE_TREE", env)
        app = KVStoreApp()
        app.init_chain(VALS, "c", app_state)
        for tx in txs:
            app.deliver_tx(tx)
        return app, app.commit()

    tree = {"kvstore": {"commit_backend": "tree"}}
    buckets = {"kvstore": {"commit_backend": "buckets"}}
    _, by_env_tree = run(None, "on")
    _, by_env_buckets = run(None, None)
    assert by_env_tree != by_env_buckets         # different by design
    app, got = run(tree, "off")                  # the genesis wins
    assert got == by_env_tree and app._tree is not None
    app, got = run(buckets, "on")
    assert got == by_env_buckets and app._tree is None
    assert run(tree, "on")[1] == by_env_tree
    assert run({"other_app": 1}, None)[1] == by_env_buckets
    with pytest.raises(ValueError, match="commit_backend"):
        run({"kvstore": {"commit_backend": "iavl"}}, None)


@pytest.mark.parametrize("spoil", ["digest", "count", "cut"])
def test_a_records_file_that_is_not_the_genesis_one_stops_initchain(
        spoil, tmp_path):
    pairs = pairs_of(5, 200)
    path = str(tmp_path / "r.bin")
    entry = records.write_records(path, pairs)
    assert list(records.read_records(entry)) == [
        (bytes(k), bytes(v)) for k, v in pairs]
    if spoil == "digest":
        entry = dict(entry, sha256=hashlib.sha256(b"other").hexdigest())
    elif spoil == "count":
        entry = dict(entry, count=entry["count"] + 1)
    else:
        with open(path, "r+b") as f:
            f.truncate(entry["count"] * 20)
    app = KVStoreApp()
    with pytest.raises(records.RecordsError):
        app.init_chain(VALS, "c", {"kvstore": {"commit_backend": "tree",
                                               "records": entry}})
    assert app.height == 0 and app.app_hash == b""    # nothing committed


def test_a_proven_read_tells_an_empty_value_from_an_absent_key(monkeypatch):
    from benchmark import treeref
    monkeypatch.delenv("TM_TPU_STATE_TREE", raising=False)
    app = KVStoreApp()
    app.init_chain(VALS, "c", {"kvstore": {"commit_backend": "tree"}})
    for tx in (b"k=", b"other=1", b"third=3"):
        app.deliver_tx(tx)
    app_hash = app.commit()
    empty = app.query("/store", b"k", 0, True)
    absent = app.query("/store", b"nobody", 0, True)
    assert empty.value == absent.value == b""
    assert (empty.log, absent.log) == ("exists", "does not exist")
    p_empty = statetree.proof_from_bytes(empty.proof)
    p_absent = statetree.proof_from_bytes(absent.proof)
    assert p_empty.present and not p_absent.present
    statetree.verify(p_empty, b"k", b"", app_hash)
    statetree.verify(p_absent, b"nobody", None, app_hash)
    assert treeref.verify(empty.proof, b"k", b"", app_hash) is True
    assert treeref.verify(absent.proof, b"nobody", None, app_hash) is False
    # neither proof passes for the other claim
    with pytest.raises(treeref.Rejected):
        treeref.verify(empty.proof, b"k", None, app_hash)
    with pytest.raises(treeref.Rejected):
        treeref.verify(absent.proof, b"nobody", b"", app_hash)
    with pytest.raises(statetree.ProofError):
        statetree.verify(p_absent, b"nobody", b"x", app_hash)


def test_the_tree_records_its_commits_loads_queries_and_proofs(monkeypatch):
    monkeypatch.delenv("TM_TPU_STATE_TREE", raising=False)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    trace.TRACER.clear()
    t0 = time.perf_counter()
    try:
        def proofs(kind):
            return telemetry.value("statetree_proofs_total",
                                   {"kind": kind}) or 0.0
        before = {k: proofs(k) for k in ("inclusion", "absence")}
        app = KVStoreApp()
        app.init_chain(VALS, "c", {"kvstore": {"commit_backend": "tree"}})
        app.restore_items([(b"a%d" % i, b"v") for i in range(30)], 7)
        for tx in (b"a1=w", b"a2=w", b"a1=x"):
            app.deliver_tx(tx)
        app.commit()
        app.query("/store", b"a1", 0, True)
        app.query("/store", b"a1", 7, False)
        app.query("/store", b"zz", 0, True)
        t1 = time.perf_counter()
        loads, _ = trace.TRACER.between("tree.load", t0, t1)
        commits, _ = trace.TRACER.between("tree.commit", t0, t1)
        queries, _ = trace.TRACER.between("app.query", t0, t1)
        assert [r["args"] for r in loads] == [{"records": 30, "bytes": 30}]
        # the restore's commit found every node hashed; the block's
        # rehashed two leaves
        assert [(r["req"], r["args"]) for r in commits] == [
            (7, {"dirty_leaves": 0}), (8, {"dirty_leaves": 2})]
        assert [(r["req"], r["args"]["prove"]) for r in queries] == [
            (8, 1), (7, 0), (8, 1)]
        assert {k: proofs(k) - before[k] for k in before} == {
            "inclusion": 1.0, "absence": 1.0}
        # a commit's and a query's fields ride in the ring's columns:
        # no object an event, but for the one load's two args
        assert len(trace.TRACER._objs) == 1
    finally:
        trace.TRACER.clear()
        telemetry.set_enabled(was)


def test_no_sha_wave_goes_to_the_device_whatever_is_imported(monkeypatch):
    import jax      # noqa: F401  (loaded in this process: it decides nothing)
    from tendermint_tpu.ops import merkle

    def never(payloads):
        raise AssertionError("a wave went to the device")
    monkeypatch.setattr(merkle, "sha256_many_device", never)
    rng = random.Random(1)
    wave = [rng.randbytes(65) for _ in range(merkle._SHA_WAVE_MAX + 700)]
    want = [hashlib.sha256(p).digest() for p in wave]
    assert merkle.sha256_many_host(wave) == want     # cut in two, in order
    assert merkle.sha256_many_host(wave[:600]) == want[:600]
    monkeypatch.undo()
    # the plane stays, for chip_smoke.py and scripts/sha_waves.py
    assert merkle.sha256_many_device(wave[:600]) == want[:600]
