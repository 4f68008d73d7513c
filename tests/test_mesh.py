"""parallel/mesh.py sharded kernels on the 8-device virtual CPU mesh.

Validates the multi-chip story end to end: the shard_map-wrapped verify
kernel agrees with the unsharded kernel (including invalid signatures
landing on different shards), the all_gather Merkle tree-finish agrees
with the host spec for non-power-of-two leaf counts, and verify_step —
the dryrun's full sharded step — runs on the conftest mesh.
"""

import random

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.ops import ed25519, merkle
from tendermint_tpu.parallel.mesh import (make_mesh, sharded_merkle_root,
                                          sharded_verify_kernel, verify_step)
from tendermint_tpu.utils import ed25519_ref as ref

rng = random.Random(41)

# Fail loudly (not skip) if conftest's platform steering broke: the whole
# multi-chip story depends on these tests actually running on 8 devices.
# A fixture (not module-level) so deselected runs don't pay backend init.
@pytest.fixture(autouse=True, scope="module")
def _require_virtual_mesh():
    assert jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8, \
        f"test mesh misconfigured: {jax.devices()}"


def signed_batch(n, tamper=()):
    """n (pub, msg, sig) triples; indices in `tamper` get a corrupted sig."""
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = rng.randbytes(32)
        m = b"mesh test %d" % i
        sig = ref.sign(seed, m)
        if i in tamper:
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        pubs.append(ref.public_key(seed))
        msgs.append(m)
        sigs.append(sig)
    return pubs, msgs, sigs


def test_sharded_verify_matches_unsharded():
    mesh = make_mesh(8)
    n = 16
    # invalid sigs spread over different shards (2 sigs per device)
    tamper = {1, 7, 14}
    pubs, msgs, sigs = signed_batch(n, tamper)
    pk, rb, sb, hb, pre = ed25519.prepare_batch_bytes(pubs, msgs, sigs)
    assert pre.all()
    args = (jnp.asarray(pk), jnp.asarray(rb),
            jnp.asarray(sb), jnp.asarray(hb))
    got = np.asarray(sharded_verify_kernel(mesh)(*args))
    want = np.asarray(ed25519.verify_kernel(
        args[0], args[1], ed25519.bits_from_bytes_dev(args[2]),
        ed25519.bits_from_bytes_dev(args[3])))
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, want)
    for i in range(n):
        assert got[i] == (i not in tamper), i


def test_sharded_kernel_on_smaller_mesh():
    """make_mesh(n < all devices) shards correctly. Exercised through
    the MERKLE kernel: the 2-device ed25519 SPMD program costs a ~40s
    extra compile for no additional coverage (the verify kernel's
    sharding is already proven on the 8-device mesh above; mesh-width
    partitioning is kernel-agnostic in shard_map)."""
    mesh = make_mesh(2)
    items = [bytes([i]) * 9 for i in range(16)]
    digests = merkle.pad_digests(np.stack(
        [np.frombuffer(merkle.leaf_hash(it), np.uint8) for it in items]))
    got = np.asarray(sharded_merkle_root(mesh)(
        jnp.asarray(digests), len(items))).tobytes()
    assert got == merkle.root_host(items)


@pytest.mark.parametrize("n_leaves", [8, 9, 13, 16, 100, 128])
def test_sharded_merkle_root_matches_host(n_leaves):
    # padded size must be divisible by the mesh size (>= 8 leaves here);
    # sub-mesh-width trees take the unsharded kernel path in production
    mesh = make_mesh(8)
    items = [rng.randbytes(rng.randrange(1, 40)) for _ in range(n_leaves)]
    digests = merkle.pad_digests(np.stack(
        [np.frombuffer(merkle.leaf_hash(it), np.uint8) for it in items]))
    root = sharded_merkle_root(mesh)
    got = np.asarray(root(jnp.asarray(digests), n_leaves)).tobytes()
    assert got == merkle.root_host(items), n_leaves


def test_verify_step_end_to_end():
    mesh = make_mesh(8)
    step = verify_step(mesh)
    n = 16
    pubs, msgs, sigs = signed_batch(n)
    pk, rb, sb, hb, pre = ed25519.prepare_batch_bytes(pubs, msgs, sigs)
    assert pre.all()
    leaves = [bytes([i]) * 8 for i in range(n)]
    digests = merkle.pad_digests(np.stack(
        [np.frombuffer(merkle.leaf_hash(it), np.uint8) for it in leaves]))
    ok, root = step(jnp.asarray(pk), jnp.asarray(rb), jnp.asarray(sb),
                    jnp.asarray(hb), jnp.asarray(digests), n)
    assert np.asarray(ok).all()
    assert np.asarray(root).tobytes() == merkle.root_host(leaves)


# ----------------------------------------------- product path (VERDICT r2 #1)

def test_batch_verifier_mesh_knob():
    """BatchVerifier(mesh=...) builds the mesh lazily, its verdicts
    agree with the scalar oracle, and the dispatch is counted under the
    kernel that served it — the production multi-chip wiring
    (models/verifier.py), not a bespoke kernel call."""
    from tendermint_tpu.models.verifier import BatchVerifier

    # 16 items: same padded batch shape as the other 8-dev mesh tests,
    # so the (cached) kernel closure compiles this shape exactly once
    # across the file
    pubs, msgs, sigs = signed_batch(16, tamper={3})
    items = list(zip(pubs, msgs, sigs))

    v = BatchVerifier("jax", mesh="8")
    assert v._mesh is None and v.mesh_devices == 0  # lazy until dispatch
    k0 = ed25519.predecomp_stats()
    ok = v.verify(items)
    assert v.mesh_devices == 8 and v._mesh is make_mesh(8)
    assert ok.tolist() == [i != 3 for i in range(16)]
    k1 = ed25519.predecomp_stats()
    assert k1["mesh_jnp"] == k0["mesh_jnp"] + 1
    assert k1["jnp_full"] == k0["jnp_full"]
    assert "jnp_full[16/8]" in k1["first_call_s"]

    # auto on this 8-device host also shards 8-wide (same cached mesh,
    # hence the same compiled program)
    va = BatchVerifier("jax", mesh="auto")
    assert va.verify(items).tolist() == ok.tolist()
    assert va.mesh_devices == 8 and va._mesh is v._mesh

    # off / single-chip spec -> plain kernel path. 8 items: the plain
    # @8 jnp shape is already compiled by test_ed25519, so this arm
    # proves the ROUTING without paying a fresh @16 plain compile
    voff = BatchVerifier("jax", mesh="off")
    assert voff.verify(items[:8]).tolist() == ok.tolist()[:8]
    assert voff.mesh_devices == 0 and voff._mesh is None
    assert ed25519.predecomp_stats()["jnp_full"] == k1["jnp_full"] + 1


def test_the_predecompressed_path_takes_its_rows_before_the_shard():
    """Under a mesh a batch over resident keys is handed ROWS, taken
    from the key table on the host before the batch axis is split (the
    table is never sharded): full, fill, hit give the verdicts of the
    unsharded oracle, the `pre` dispatches among them."""
    mesh = make_mesh(8)
    pubs, msgs, sigs = signed_batch(16, tamper={2, 11})
    want = [i not in (2, 11) for i in range(16)]
    caches = (ed25519._predecomp, ed25519._predecomp_seen,
              ed25519._predecomp_memo)
    gate = ed25519._PREDECOMP_MIN_BATCH
    ed25519._PREDECOMP_MIN_BATCH = 8
    for c in caches:
        c.clear()
    try:
        for outcome in ("full", "fill", "hit", "hit"):
            s0 = ed25519.predecomp_stats()
            got = ed25519.verify_batch(pubs, msgs, sigs, mesh=mesh)
            assert got.tolist() == want, outcome
            s1 = ed25519.predecomp_stats()
            assert s1[outcome] == s0[outcome] + 1
            assert s1["mesh_jnp"] == s0["mesh_jnp"] + 1
        assert "jnp_pre[16/8]" in s1["first_call_s"]
    finally:
        ed25519._PREDECOMP_MIN_BATCH = gate
        for c in caches:
            c.clear()


def test_batch_verifier_mesh_spec_errors():
    from tendermint_tpu.models.verifier import BatchVerifier
    # spec validation is eager (at construction, i.e. node startup) ...
    with pytest.raises(ValueError):
        BatchVerifier("jax", mesh="3")
    with pytest.raises(ValueError):
        BatchVerifier("jax", mesh="bogus")
    # ... only the device-count check needs jax and stays lazy, and it
    # raises RuntimeError, which no verify-path caller catches as a
    # bad-input signal
    with pytest.raises(RuntimeError):
        BatchVerifier("jax", mesh="64")._resolve_mesh()


def test_mesh_auto_noop_on_single_device_host(monkeypatch):
    """mesh='auto' on a 1-device host is a no-op: no mesh, no
    min-bucket bump, scalar-friendly defaults untouched — and an
    explicit mesh=N beyond the host raises the loud RuntimeError (the
    knob contract, not a bad-peer-data signal)."""
    from tendermint_tpu.models.verifier import BatchVerifier

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    v = BatchVerifier("jax", mesh="auto")
    v._resolve_mesh()
    assert v._mesh_resolved
    assert v._mesh is None and v.mesh_devices == 0
    with pytest.raises(RuntimeError):
        BatchVerifier("jax", mesh="2")._resolve_mesh()


def test_coalesced_batches_pad_mesh_divisible(monkeypatch):
    """Two concurrent calls under the threshold, backend jax, each
    dispatched where it is made: both land on the sharded kernel with a
    mesh-divisible padded axis (the mesh-derived min bucket flows
    through _verify_async_direct), so every dispatched shape is a power
    of two >= the mesh width. Forced 4-device mesh on the 8-device
    host."""
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.models.verifier import BatchVerifier

    pubs, msgs, sigs = signed_batch(8, tamper={5})
    items = list(zip(pubs, msgs, sigs))

    v = BatchVerifier("jax", mesh="4")
    v._resolve_mesh()
    assert v.mesh_devices == 4

    shapes = []
    inner = ed25519._dispatch

    def recording(variant, mesh, *args):
        assert mesh is v._mesh
        shapes.append(int(args[0].shape[0]))
        return inner(variant, mesh, *args)

    monkeypatch.setattr(ed25519, "_dispatch", recording)
    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(v.verify, items[:4]),
                pool.submit(v.verify, items[4:])]
        first, second = futs[0].result(), futs[1].result()
    assert first.tolist() == [True] * 4
    assert second.tolist() == [True, False, True, True]  # tamper at 5
    assert v.stats == {"calls": 2, "sigs": 8, "jax_sigs": 8}
    assert len(shapes) == 2, shapes
    assert all(s % 4 == 0 and s & (s - 1) == 0 for s in shapes), shapes


def test_mesh_telemetry_surfaces():
    """tm_verifier_mesh_devices reports the active mesh width and every
    sharded dispatch lands in tm_mesh_dispatch_total +
    tm_mesh_shard_occupancy (the new mesh catalog, also policed by the
    metrics lint)."""
    from tendermint_tpu import telemetry
    from tendermint_tpu.models.verifier import BatchVerifier

    pubs, msgs, sigs = signed_batch(16)
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        v = BatchVerifier("jax", mesh="8")
        d0 = telemetry.value("mesh_dispatch_total",
                             {"kind": "verify"}) or 0
        assert v.verify(list(zip(pubs, msgs, sigs))).all()
        assert telemetry.value("verifier_mesh_devices") == 8
        assert telemetry.value("mesh_dispatch_total",
                               {"kind": "verify"}) == d0 + 1
        occ = telemetry.value("mesh_shard_occupancy")
        assert occ["count"] >= 1
        # a full 16-item batch in a 16-wide bucket: occupancy 1.0
        assert occ["sum"] >= 1.0
    finally:
        telemetry.set_enabled(was)


def test_root_host_mesh_dispatch_bit_equality(monkeypatch):
    """ops.merkle's host-facing roots (tx root, part-set root) route
    through the sharded device kernel when a mesh is active, and the
    bytes match the native/hashlib host path exactly. 100 leaves ->
    the padded-128 shape the parametrized kernel tests already
    compiled."""
    from tendermint_tpu import telemetry

    items = [rng.randbytes(rng.randrange(1, 40)) for _ in range(100)]
    digests = [merkle.leaf_hash(it) for it in items]
    want = merkle.root_host(items)  # TM_TPU_MESH=off in conftest: host

    kern = sharded_merkle_root(make_mesh(8))
    monkeypatch.setattr(merkle, "_mesh_state", (kern, 8))
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        d0 = telemetry.value("mesh_dispatch_total",
                             {"kind": "merkle"}) or 0
        assert merkle.root_host(items) == want
        # both digest-list and flat-blob forms take the mesh path
        assert merkle.root_from_digests_host(digests) == want
        assert merkle.root_from_digests_host(b"".join(digests)) == want
        assert telemetry.value("mesh_dispatch_total",
                               {"kind": "merkle"}) == d0 + 3
        assert telemetry.value("merkle_roots_total",
                               {"impl": "mesh"}) >= 3
    finally:
        telemetry.set_enabled(was)
    # sub-threshold trees stay on host (no mesh dispatch)
    small = [b"x"] * (merkle._MESH_MIN_LEAVES - 1)
    assert merkle.root_host(small) == merkle.root_from_digests_host(
        [merkle.leaf_hash(b"x")] * len(small))


def test_merkle_mesh_env_resolution(monkeypatch):
    """TM_TPU_MESH=N resolves the merkle mesh dispatch lazily through
    the same parallel.mesh spec grammar the verifier uses (env wins,
    power-of-two validation, loud overshoot)."""
    items = [bytes([i]) * 11 for i in range(100)]
    want = merkle.root_host(items)  # resolved off: host path

    monkeypatch.setenv("TM_TPU_MESH", "8")
    monkeypatch.setattr(merkle, "_mesh_state", None)
    assert merkle.root_host(items) == want
    kern, ndev = merkle._mesh_state
    assert ndev == 8 and kern is not None

    # overshooting the host fails loudly, same contract as the verifier
    monkeypatch.setenv("TM_TPU_MESH", "64")
    monkeypatch.setattr(merkle, "_mesh_state", None)
    with pytest.raises(RuntimeError):
        merkle.root_host(items)


def test_fast_sync_window_verifies_through_mesh():
    """fast-sync's _sync_window drains its batched window through a
    mesh-sharded BatchVerifier injected via BlockExecutor — the node
    config path (base.verifier_mesh) on a multi-device host."""
    from test_fast_sync import build_chain
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.abci.types import ValidatorUpdate
    from tendermint_tpu.blockchain import BlockchainReactor, BlockPool
    from tendermint_tpu.models.verifier import BatchVerifier
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.storage import BlockStore, MemDB, StateStore
    from tendermint_tpu.types import (GenesisDoc, GenesisValidator, PrivKey)

    key = PrivKey.generate(b"\x2a" * 32)
    gen = GenesisDoc(chain_id="mesh-fs", genesis_time_ns=1,
                     validators=[GenesisValidator(key.pubkey.ed25519, 10)])
    # 17 blocks -> a 16-signature window: shares the compiled batch
    # shape with the rest of the file (one compile per shape per mesh)
    _, _, src_store, gen = build_chain(gen, key, 17)

    conns = AppConns(local_client_creator(KVStoreApp()))
    state_store = StateStore(MemDB())
    store = BlockStore(MemDB())
    state = state_store.load_or_genesis(gen)
    conns.consensus.init_chain(
        [ValidatorUpdate(v.pubkey, v.voting_power)
         for v in state.validators.validators], gen.chain_id)
    verifier = BatchVerifier("jax", mesh="8")
    exec_ = BlockExecutor(state_store, conns.consensus, verifier=verifier)

    reactor = BlockchainReactor(state, exec_, store, fast_sync=True,
                                verify_window=16)
    pool = BlockPool(start_height=1, send_request=lambda p, h: True,
                     on_peer_error=lambda p, r: None)
    reactor.pool = pool
    pool.set_peer_height("src", src_store.height())
    pool.make_next_requests()
    for h in range(1, src_store.height() + 1):
        assert pool.add_block("src", src_store.load_block(h), 100)

    while reactor._sync_window():
        pass
    # synced to tip-1 (tip has no child commit in the window)
    assert store.height() == src_store.height() - 1
    assert verifier.mesh_devices == 8, "window did not use the mesh kernel"
    assert verifier.stats["jax_sigs"] > 0
