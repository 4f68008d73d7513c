"""Batched binary Merkle trees on TPU — replaces tmlibs/merkle.

The reference builds trees recursively one RIPEMD160 call at a time
(types/tx.go:33-46, types/part_set.go:110). This design is level-batched
and fixed-shape instead:

Spec (deliberately TPU-first, not wire-compatible with the reference):
  leaf     = SHA256(0x00 || item_bytes)
  node     = SHA256(0x01 || left || right)
  pad leaf = 32 zero bytes (unreachable as a real leaf digest)
  tree     = leaves padded to the next power of two, perfect binary tree
  root     = SHA256(0x02 || uint64_le(n_leaves) || tree_root)

Padding to a power of two makes every level a dense [m, 64]-shaped batch
(one vmapped 2-block SHA-256 per level) with no odd-promote control flow,
and the size-binding outer hash removes padding ambiguity. Proofs all have
depth log2(padded_n), verified leaf-up.

Host-side mirrors (hashlib) of every device function keep CPU-only nodes
and proof verification bit-identical.
"""

from __future__ import annotations

import functools
import hashlib
import struct
import threading

import numpy as np

from tendermint_tpu import telemetry

# jax (and ops.sha256, which pulls it in) is imported LAZILY inside the
# device functions: merkle is imported by the core data model
# (types/block.py), and a plain CPU node — every e2e/crash-matrix
# subprocess — must not pay the multi-second jax import for host-side
# hashing it never uses. (telemetry is stdlib-only and safe here.)

EMPTY_DIGEST = b"\x00" * 32  # padding leaf

# Each public root/proof entry point counts once; `impl` says whether
# the native C++ tree builder served it or the hashlib fallback ran.
_m_roots = telemetry.counter(
    "merkle_roots_total", "Merkle roots computed on host", ("impl",))
_m_leaves = telemetry.histogram(
    "merkle_leaves", "Leaves per host-side Merkle root",
    buckets=telemetry.POW2_BUCKETS)
_m_proofs = telemetry.counter(
    "merkle_proofs_total", "Merkle proofs computed on host")


# ---------------------------------------------------------------------------
# Mesh dispatch — big roots shard over the verifier's device mesh
# ---------------------------------------------------------------------------
# The same TM_TPU_MESH knob that shards BatchVerifier batches routes the
# host-facing root entry points (tx root, part-set root, results hash)
# through parallel/mesh.py's sharded Merkle kernel once the tree is big
# enough to amortize a device dispatch. Sub-threshold trees — small
# part sets, header field maps — stay on the native/hashlib host path.

# leaves below this stay on host (mirrors the verifier's auto_threshold
# split: interactive sizes skip the dispatch round trip entirely)
_MESH_MIN_LEAVES = 64
_mesh_lock = threading.Lock()
# None = unresolved; (kernel, n_devices) once resolved ((None, 1) = no
# mesh). Tests monkeypatch this to force a kernel in.
_mesh_state: "tuple | None" = None


def _mesh_root_kernel() -> "tuple":
    """(sharded root kernel | None, n_devices), resolved lazily.

    Resolution mirrors BatchVerifier._resolve_mesh (same TM_TPU_MESH
    grammar via parallel.mesh) with one extra guard: under the default
    spec 'auto' the mesh is only considered when jax is ALREADY
    imported in this process — a plain CPU node hashing on host must
    never pay the multi-second jax init for a Merkle root. That
    undecided state is NOT cached, so the first root after something
    else brings jax up (a batched verify) resolves for real. An
    explicit TM_TPU_MESH=N opts in unconditionally and raises, loudly,
    when N exceeds the devices present — same contract as the
    verifier. A backend that fails to come up raises too: it is never
    remembered as "no mesh"."""
    global _mesh_state
    st = _mesh_state
    if st is not None:
        return st
    with _mesh_lock:
        if _mesh_state is not None:
            return _mesh_state
        import sys
        from tendermint_tpu.utils import knobs
        from tendermint_tpu.parallel import mesh as pmesh
        spec = pmesh.parse_mesh_spec(
            knobs.knob_str("TM_TPU_MESH", default="auto"))
        if spec == "off":
            _mesh_state = (None, 1)
            return _mesh_state
        if spec == "auto" and "jax" not in sys.modules:
            return (None, 1)  # undecided — do not cache
        import jax
        n = pmesh.resolve_mesh_size(spec, len(jax.devices()))
        if n < 2:
            _mesh_state = (None, 1)
        else:
            _mesh_state = (pmesh.sharded_merkle_root(pmesh.make_mesh(n)),
                           n)
        return _mesh_state


def _mesh_root_from_digest_rows(rows: np.ndarray, n: int) -> "bytes | None":
    """Sharded device root of uint8[n, 32] leaf digests, or None when
    no mesh is active / the tree is too small for its width."""
    if n < _MESH_MIN_LEAVES:
        return None
    kernel, ndev = _mesh_root_kernel()
    if kernel is None or _padded_size(n) < ndev:
        return None
    import jax.numpy as jnp  # already imported per the resolve policy
    from tendermint_tpu.parallel import mesh as pmesh
    padded = pad_digests(rows)
    pmesh.record_dispatch("merkle", n, padded.shape[0])
    if telemetry.enabled():
        _m_roots.labels("mesh").inc()
        _m_leaves.observe(n)
    return np.asarray(kernel(jnp.asarray(padded), n)).tobytes()


# ---------------------------------------------------------------------------
# Host (hashlib) spec implementation — the semantic reference
# ---------------------------------------------------------------------------

def leaf_hash(item: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + item).digest()


def node_hash(left: bytes, right: bytes) -> bytes:
    return hashlib.sha256(b"\x01" + left + right).digest()


def _final_hash(n: int, tree_root: bytes) -> bytes:
    return hashlib.sha256(b"\x02" + struct.pack("<Q", n) + tree_root).digest()


def _padded_size(n: int) -> int:
    m = 1
    while m < n:
        m *= 2
    return m


def root_host(items: list[bytes]) -> bytes:
    """Merkle root of raw items. Big trees shard over the active device
    mesh (TM_TPU_MESH, see _mesh_root_kernel); otherwise the native C++
    tree builder (native/hostops.cpp) when available — one C call per
    tree instead of 2n hashlib round trips."""
    n = len(items)
    from tendermint_tpu import native
    if n >= _MESH_MIN_LEAVES and _mesh_root_kernel()[0] is not None:
        # leaves are variable-length: hashed on the host (one native
        # call when the extension is there), the tree on the mesh
        leaves = native.sha256_batch([b"\x00" + it for it in items]) \
            or [leaf_hash(it) for it in items]
        rows = np.frombuffer(b"".join(leaves), np.uint8).reshape(n, 32)
        out = _mesh_root_from_digest_rows(rows, n)
        if out is not None:
            return out
    out = native.merkle_root(items)
    if out is not None:
        if telemetry.enabled():
            _m_roots.labels("native").inc()
            _m_leaves.observe(len(items))
        return out
    return root_from_digests_host([leaf_hash(it) for it in items])


def root_from_digests_host(digests) -> bytes:
    """digests: list of 32B hashes or a flat bytes-like blob (len%32==0,
    passed through to the native kernel without a join/copy)."""
    flat = isinstance(digests, (bytes, bytearray, memoryview))
    n = len(digests) // 32 if flat else len(digests)
    if n == 0:
        return _final_hash(0, EMPTY_DIGEST)
    if n >= _MESH_MIN_LEAVES and _mesh_root_kernel()[0] is not None:
        if flat:
            rows = np.frombuffer(bytes(digests), np.uint8).reshape(n, 32)
        else:
            rows = np.stack([np.frombuffer(d, np.uint8) for d in digests])
        out = _mesh_root_from_digest_rows(rows, n)
        if out is not None:
            return out
    if telemetry.enabled():
        _m_leaves.observe(n)
    from tendermint_tpu import native
    out = native.merkle_root_from_digests(
        digests if flat else list(digests))
    if out is not None:
        _m_roots.labels("native").inc()
        return out
    _m_roots.labels("host").inc()
    if flat:
        digests = [bytes(digests[32 * i:32 * (i + 1)]) for i in range(n)]
    level = list(digests) + [EMPTY_DIGEST] * (_padded_size(n) - n)
    while len(level) > 1:
        level = [node_hash(level[i], level[i + 1]) for i in range(0, len(level), 2)]
    return _final_hash(n, level[0])


def root_from_repeated_digest(digest: bytes, n: int) -> bytes:
    """Root over n copies of one leaf digest in O(log n) — byte-equal
    to root_from_digests_host(digest * n). Levels of such a tree are
    runs of at most a handful of distinct values (the repeated digest,
    zero-padding, and their boundary combinations), so each level is a
    run-length merge instead of n hashes. This is the results-hash of
    the common all-txs-OK block, where every DeliverTx leaf encodes
    identically (types/results.go:20-49 hashes only code+data)."""
    if n <= 0:
        return _final_hash(0, EMPTY_DIGEST)
    runs = [(digest, n)]
    pad = _padded_size(n) - n
    if pad:
        runs.append((EMPTY_DIGEST, pad))
    total = n + pad
    while total > 1:
        new_runs: list[tuple[bytes, int]] = []
        carry = None
        for d, c in runs:
            if carry is not None:
                new_runs.append((node_hash(carry, d), 1))
                carry = None
                c -= 1
            if c >= 2:
                new_runs.append((node_hash(d, d), c // 2))
            if c % 2:
                carry = d
        assert carry is None  # padded totals stay even at every level
        # coalesce adjacent equal runs so the run count stays O(1)
        runs = []
        for d, c in new_runs:
            if runs and runs[-1][0] == d:
                runs[-1] = (d, runs[-1][1] + c)
            else:
                runs.append((d, c))
        total //= 2
    return _final_hash(n, runs[0][0])


def proof_host(items: list[bytes], index: int):
    """Returns (root, aunts) — aunts leaf-up, each 32 bytes."""
    n = len(items)
    assert 0 <= index < n
    _m_proofs.inc()
    from tendermint_tpu import native
    native_out = native.merkle_proof(items, index)
    if native_out is not None:
        return native_out
    level = [leaf_hash(it) for it in items] + \
        [EMPTY_DIGEST] * (_padded_size(n) - n)
    aunts = []
    idx = index
    while len(level) > 1:
        aunts.append(level[idx ^ 1])
        level = [node_hash(level[i], level[i + 1]) for i in range(0, len(level), 2)]
        idx //= 2
    return _final_hash(n, level[0]), aunts


def tree_proofs_host(items: list[bytes]):
    """(root, [aunts per item]) — every item's proof from one tree
    build. Native-backed; the fallback builds the level lists once and
    extracts all proofs from them (never one tree per item)."""
    n = len(items)
    from tendermint_tpu import native
    native_out = native.merkle_tree_proofs(items)
    if native_out is not None:
        return native_out
    level = [leaf_hash(it) for it in items] + \
        [EMPTY_DIGEST] * (_padded_size(max(n, 1)) - n)
    levels = []
    while len(level) > 1:
        levels.append(level)
        level = [node_hash(level[i], level[i + 1])
                 for i in range(0, len(level), 2)]
    root = _final_hash(n, level[0] if level else EMPTY_DIGEST)
    proofs = []
    for index in range(n):
        idx = index
        aunts = []
        for lvl in levels:
            aunts.append(lvl[idx ^ 1])
            idx //= 2
        proofs.append(aunts)
    return root, proofs


# a longer wave is cut into dispatches of this many payloads: the packed
# copy of one (4 MB of 1 KB values) stays in the cache where a whole
# level of a bulk load (a million values) faulted in a gigabyte twice
_SHA_WAVE_MAX = 4096
_m_sha_batches = telemetry.counter(
    "merkle_sha_batches_total", "Batched SHA-256 dispatches", ("impl",))


def sha256_many_host(payloads: list) -> list[bytes]:
    """One SHA-256 digest per payload, batched: the statetree's rehash
    plane (a commit hands its dirty leaf and inner payloads here in
    level-sized waves, a bulk load its whole levels, cut into
    dispatches of at most _SHA_WAVE_MAX). Every wave goes to the native
    C++ batch kernel when present, else a hashlib loop; never to the
    device, whatever the process has imported: on a TPU v5e
    `sha256_many_device` lost to the native kernel at every payload
    length and wave size a store has, 1.4 to 8.6 times, plus 0.3-5 s a
    shape for its first call (PERF.md, PR 35; scripts/sha_waves.py)."""
    n = len(payloads)
    if n == 0:
        return []
    if n > _SHA_WAVE_MAX:
        out = []
        for i in range(0, n, _SHA_WAVE_MAX):
            out += sha256_many_host(payloads[i:i + _SHA_WAVE_MAX])
        return out
    from tendermint_tpu import native
    out = native.sha256_batch([bytes(p) for p in payloads])
    if out is not None:
        if telemetry.enabled():
            _m_sha_batches.labels("native").inc()
        return out
    if telemetry.enabled():
        _m_sha_batches.labels("host").inc()
    sha = hashlib.sha256
    return [sha(p).digest() for p in payloads]


def sha256_many_device(payloads: list) -> list[bytes]:
    """`sha256_many_host`'s digests of payloads of one length, through
    the jitted ops.sha256.hash_fixed on the device. Nothing in the
    program sends a wave here (see above); chip_smoke.py and
    scripts/sha_waves.py call it to check and to time the plane. Rows
    are padded to a power of two so the compiled shapes stay bounded
    (one per bucket and payload length, not one per wave)."""
    from tendermint_tpu.ops import sha256
    n, length = len(payloads), len(payloads[0])
    rows = np.zeros((_padded_size(n), length), np.uint8)
    rows[:n] = np.frombuffer(b"".join(payloads), np.uint8).reshape(
        n, length)
    if telemetry.enabled():
        _m_sha_batches.labels("device").inc()
    out = np.asarray(sha256.hash_fixed_jit(rows))
    return [out[i].tobytes() for i in range(n)]


def verify_proof_host(root: bytes, total: int, index: int, item: bytes,
                      aunts: list[bytes]) -> bool:
    if not (0 <= index < total) or _padded_size(max(total, 1)) != 1 << len(aunts):
        return False
    h = leaf_hash(item)
    idx = index
    for aunt in aunts:
        h = node_hash(aunt, h) if idx & 1 else node_hash(h, aunt)
        idx //= 2
    return _final_hash(total, h) == root


# ---------------------------------------------------------------------------
# Device (jnp) implementation — batched level-by-level
# ---------------------------------------------------------------------------

_PREFIX_LEAF = np.array([0x00], dtype=np.uint8)
_PREFIX_NODE = np.array([0x01], dtype=np.uint8)


def leaf_hash_device(items):
    """uint8[..., N, L] -> uint8[..., N, 32] (static item length L)."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import sha256
    pre = jnp.broadcast_to(jnp.asarray(_PREFIX_LEAF), items.shape[:-1] + (1,))
    return sha256.hash_fixed(jnp.concatenate([pre, items], axis=-1))


def _level_up(digests):
    """uint8[..., M, 32] -> uint8[..., M//2, 32]: one batched tree level."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import sha256
    m = digests.shape[-2]
    pairs = digests.reshape(digests.shape[:-2] + (m // 2, 64))
    pre = jnp.broadcast_to(jnp.asarray(_PREFIX_NODE), pairs.shape[:-1] + (1,))
    return sha256.hash_fixed(jnp.concatenate([pre, pairs], axis=-1))


_root_from_digests_jit = None


def root_from_digests(digests, n_leaves: int):
    """Device Merkle root: digests uint8[padded, 32] (already padded to a
    power of two with zero rows beyond n_leaves) -> uint8[32]."""
    global _root_from_digests_jit
    if _root_from_digests_jit is None:
        import jax
        _root_from_digests_jit = functools.partial(
            jax.jit, static_argnames=("n_leaves",))(_root_from_digests)
    return _root_from_digests_jit(digests, n_leaves=n_leaves)


def _root_from_digests(digests, n_leaves: int):
    import jax.numpy as jnp

    from tendermint_tpu.ops import sha256
    level = digests
    while level.shape[-2] > 1:
        level = _level_up(level)
    tree_root = level[..., 0, :]
    header = np.concatenate([
        np.array([0x02], np.uint8),
        np.frombuffer(struct.pack("<Q", n_leaves), np.uint8)])
    hdr = jnp.broadcast_to(jnp.asarray(header), tree_root.shape[:-1] + (9,))
    return sha256.hash_fixed(jnp.concatenate([hdr, tree_root], axis=-1))


def pad_digests(digests: np.ndarray) -> np.ndarray:
    """Host helper: uint8[N,32] -> uint8[padded,32] zero-padded."""
    n = digests.shape[0]
    m = _padded_size(max(n, 1))
    if m == n:
        return digests
    return np.concatenate(
        [digests, np.zeros((m - n, 32), np.uint8)], axis=0)


def root(items: list[bytes]) -> bytes:
    """Merkle root of variable-length items: host leaf hashing (variable
    shapes), device tree. The empty tree stays on host."""
    n = len(items)
    if n == 0:
        return _final_hash(0, EMPTY_DIGEST)
    import jax.numpy as jnp
    digests = np.stack(
        [np.frombuffer(leaf_hash(it), np.uint8) for it in items])
    out = root_from_digests(jnp.asarray(pad_digests(digests)), n)
    return np.asarray(out).tobytes()
