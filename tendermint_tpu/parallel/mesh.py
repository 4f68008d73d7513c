"""Sharded kernels over a jax.sharding.Mesh.

Design (scaling-book recipe): one mesh axis `batch` for the
embarrassingly-parallel signature dimension; shard_map partitions the
batch, each chip verifies its shard with the kernel the unsharded path
would pick for that many rows (ops/ed25519._dispatch), verdicts stay
sharded. The Merkle kernel reduces its local subtree per chip, then
all_gathers the 32-byte subtree roots — bytes over ICI per root are
32·n_devices, negligible.

Every sharded program goes through `jax.shard_map` (the installed
JAX's one entry point). A 1-device mesh is a degenerate no-op: the
builders hand back the plain unsharded jit kernels, so callers never
branch on mesh size.

jax itself is imported lazily (inside the builders): this module also
hosts the mesh spec helpers and the `tm_mesh_*` telemetry, which the
verifier/Merkle dispatch and the lint's metric catalog import from
plain-CPU processes that must not pay jax init.

Replaces nothing in the reference — this parallel axis does not exist
there (types/validator_set.go:240-265 is a serial loop on one core).
"""

from __future__ import annotations

import functools
import struct
from typing import Optional

import numpy as np

from tendermint_tpu import telemetry

_mesh_cache: dict = {}
_kernel_cache: dict = {}

# One dispatch = one sharded kernel launch from the verifier or the
# Merkle root plane. Occupancy is real rows / padded rows — with the
# contiguous padding layout that is also the mean per-shard fill, and
# a low value means most chips are hashing zero rows.
_m_dispatch = telemetry.counter(
    "mesh_dispatch_total", "Sharded-kernel dispatches", ("kind",))
_m_occupancy = telemetry.histogram(
    "mesh_shard_occupancy",
    "Real (unpadded) rows / padded rows per sharded dispatch",
    buckets=telemetry.RATIO_BUCKETS)


def record_dispatch(kind: str, n_real: int, n_padded: int) -> None:
    """Telemetry hook for every sharded dispatch (verifier chunk loop,
    Merkle root plane). No-op when telemetry is off."""
    if not telemetry.enabled():
        return
    _m_dispatch.labels(kind).inc()
    if n_padded > 0:
        _m_occupancy.observe(n_real / n_padded)


# ---------------------------------------------------------------------------
# Spec helpers (shared by models/verifier.py and ops/merkle.py)
# ---------------------------------------------------------------------------

def parse_mesh_spec(mesh) -> "str | int":
    """'auto' | 'off' | power-of-two int. Raises ValueError on anything
    else — callers (Node.__init__, BatchVerifier) validate the config
    knob eagerly so a typo fails at startup, not at the first batched
    verify where callers' `except ValueError` handlers would misread it
    as bad peer data."""
    s = str(mesh).strip().lower()
    if s in ("auto", ""):
        return "auto"
    if s in ("off", "0", "1", "none"):
        return "off"
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"verifier mesh must be auto|off|N, got {mesh!r}") from None
    if n < 2 or n & (n - 1):
        raise ValueError(
            f"verifier mesh size must be a power of two >= 2, got {n}")
    return n


def resolve_mesh_size(spec, n_avail: int) -> int:
    """Device count a parsed spec resolves to on an n_avail-device host.
    'off' -> 1; 'auto' -> the largest power of two that fits (sharding
    needs the padded batch axis divisible by the mesh; buckets are
    powers of two); explicit N > n_avail raises RuntimeError, which no
    verify-path caller catches as a bad-input signal."""
    if spec == "off":
        return 1
    if spec == "auto":
        n = 1
        while n * 2 <= n_avail:
            n *= 2
        return n
    if spec > n_avail:
        raise RuntimeError(
            f"verifier mesh={spec} but only {n_avail} devices present")
    return spec


def make_mesh(n_devices: Optional[int] = None):
    """Mesh over the first n devices, CACHED per device count: every
    Mesh/shard_map/jit closure combination owns its own compile cache,
    so handing out one object per size lets all callers (verifier,
    merkle dispatch, dryrun, tests) share compiled executables."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    n = n_devices or len(devs)
    if n not in _mesh_cache:
        _mesh_cache[n] = Mesh(np.array(devs[:n]), ("batch",))
    return _mesh_cache[n]


def batch_sharded(fn, mesh):
    """jit(shard_map(fn)): the leading axis of every argument and every
    result split over mesh's `batch` axis, each device running fn on
    its shard. Cached per (fn, mesh) — every jit closure owns its own
    compile cache (compiles are minutes on small CI hosts). A 1-device
    mesh hands fn back."""
    key = (fn, mesh)
    if key not in _kernel_cache:
        if mesh.devices.size == 1:
            _kernel_cache[key] = fn
        else:
            import jax
            from jax.sharding import PartitionSpec as P
            _kernel_cache[key] = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("batch"), out_specs=P("batch"),
                check_vma=False))
    return _kernel_cache[key]


def sharded_verify_kernel(mesh):
    """verify(pubkeys u8[N,32], r u8[N,32], s u8[N,32], h u8[N,32]) ->
    bool[N]: the jnp ladder from packed scalars with N sharded over
    mesh's `batch` axis — the program BatchVerifier's mesh path runs
    wherever the Pallas kernel does not apply."""
    from tendermint_tpu.ops.ed25519 import _verify_from_bytes_jnp
    return batch_sharded(_verify_from_bytes_jnp, mesh)


def sharded_merkle_root(mesh):
    """Returns root(digests u8[M,32], n_leaves) -> u8[32]; leaf digests
    sharded over `batch`, local subtree reduced per chip, subtree roots
    all_gathered and finished identically on every chip. Cached per
    mesh, like sharded_verify_kernel; a 1-device mesh degenerates to
    the plain device root."""
    key = ("merkle", mesh)
    if key in _kernel_cache:
        return _kernel_cache[key]

    from tendermint_tpu.ops import merkle, sha256

    if mesh.devices.size == 1:
        _kernel_cache[key] = merkle.root_from_digests
        return merkle.root_from_digests

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def _subtree_local(digests):
        level = digests
        while level.shape[-2] > 1:
            level = merkle._level_up(level)
        # [1, 32] per chip -> all chips see all subtree roots [n_dev, 32]
        roots = jax.lax.all_gather(level[0], "batch")
        while roots.shape[-2] > 1:
            roots = merkle._level_up(roots)
        return roots[0]

    _subtree = jax.shard_map(_subtree_local, mesh=mesh,
                             in_specs=P("batch"), out_specs=P(),
                             check_vma=False)

    @functools.partial(jax.jit, static_argnames=("n_leaves",))
    def _root(digests, n_leaves: int):
        tree_root = _subtree(digests)
        header = np.concatenate([
            np.array([0x02], np.uint8),
            np.frombuffer(struct.pack("<Q", n_leaves), np.uint8)])
        return sha256.hash_fixed(
            jnp.concatenate([jnp.asarray(header), tree_root], axis=-1))

    _kernel_cache[key] = _root
    return _root


def verify_step(mesh):
    """The flagship 'full step' over the mesh: batched commit verification
    + Merkle root of the same batch's messages-digests — i.e. everything a
    fast-sync block check does on-device, sharded. Returns
    step(pk, rb, s_bytes, h_bytes, leaf_digests, n_leaves) ->
    (ok bool[N] sharded, root u8[32] replicated)."""

    verify = sharded_verify_kernel(mesh)
    root = sharded_merkle_root(mesh)

    def step(pk, rb, s_bytes, h_bytes, leaf_digests, n_leaves: int):
        return verify(pk, rb, s_bytes, h_bytes), root(leaf_digests, n_leaves)

    return step
