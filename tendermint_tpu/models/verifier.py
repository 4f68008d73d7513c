"""BatchVerifier — the framework-wide signature verification boundary.

Reference behavior being replaced (SURVEY.md §2.9, BASELINE.md): every vote
and commit verification calls PubKey.VerifyBytes one signature at a time
(types/vote_set.go:189, types/validator_set.go:257). Here, all call sites
(VoteSet.add_vote, ValidatorSet.verify_commit, fast-sync, lite client)
funnel into one API:

    verifier.verify(items: list[(pubkey, msg, sig)]) -> bool[N]

Backends:
  "jax"    — ops/ed25519.py batch kernel; the one TPU chip XLA targets, or
             CPU XLA when no TPU is present. Chunked to BATCH_CHUNK to stay
             in VMEM (large monolithic batches fall off a perf cliff).
  "python" — scalar host loop, routed by key type through
             types/keys.verify_any (OpenSSL ed25519 with the pure
             RFC 8032 oracle as fallback and for OpenSSL's
             leniency-gap encodings; secp256k1 via ECDSA).
  "auto"   — scalar at or below auto_threshold (default 128, env
             TM_TPU_AUTO_THRESHOLD), batch above: the dual-path split
             SURVEY.md §7 calls for — interactive votes and small
             commits stay off the dispatch round trip, bulk paths
             (fast-sync windows, lite chains, large commits) batch.

Multi-chip: `mesh="auto"` (the default via TM_TPU_MESH / config
`base.verifier_mesh`) makes the verifier shard its batches over every
available device (parallel/mesh.batch_sharded around the kernel the
unsharded path would take) — resolved LAZILY on the first jax-path
dispatch so scalar verifies never pay jax backend init, and a no-op
when only one device exists. `mesh=N` forces an N-device mesh;
`mesh="off"` disables sharding.

One way from a call to its verdicts: `verify_async` decides from the
backend and the batch's size alone. A call of 1 to auto_threshold
signatures under "auto" or "python" (a live vote, a proposal, a small
set's LastCommit) is verified on the host by the thread that first
asks for its verdicts; every other call is dispatched where it is
made, and a device batch goes out through the one chunk loop of
`_dispatch_direct`.

`stats` counts what this verifier was asked and what it sent to the
device; which kernel served each device dispatch is counted
process-wide in ops/ed25519.predecomp_stats().
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
import time
from typing import Sequence

import numpy as np

from tendermint_tpu import telemetry
from tendermint_tpu.telemetry import trace
# import-light: parallel.mesh only pulls jax inside its kernel builders,
# so the spec helpers + tm_mesh_* instruments cost nothing at import
from tendermint_tpu.parallel import mesh as _pmesh
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.utils import knobs

# The paper's headline metric is sig-verifies/sec/chip; these families
# record exactly what that decomposes into: how big the batches arriving
# at the boundary are, which backend the routing policy picked, how full
# the padded device chunks run, and the dispatch->resolve wall time
# (docs/observability.md has the catalog).
_m_batch_size = telemetry.histogram(
    "verifier_batch_size", "Signatures per verify() call",
    buckets=telemetry.POW2_BUCKETS)
_m_calls = telemetry.counter(
    "verifier_calls_total", "verify() calls by chosen backend",
    ("backend",))
_m_sigs = telemetry.counter(
    "verifier_sigs_total", "Signatures verified by backend", ("backend",))
_m_dispatch = telemetry.histogram(
    "verifier_dispatch_seconds",
    "Wall time from verify dispatch to resolved verdicts", ("backend",))
_m_occupancy = telemetry.histogram(
    "verifier_chunk_occupancy",
    "Per-chunk fill ratio vs the padded power-of-two bucket",
    buckets=telemetry.RATIO_BUCKETS)
_m_mesh_devices = telemetry.gauge(
    "verifier_mesh_devices",
    "Devices in the verifier's active sharding mesh (0 = unsharded)")
# ed25519 predecompression cache (ops/ed25519): registered HERE so the
# import-light lint can see the families without importing jax; the
# ops module increments them lazily. hit = batch fully served from
# the key table's rows, by index (pre kernel, no sqrt); fill =
# repeat-traffic batch decompressed once + rows stored in the table;
# full = mostly-unseen batch routed to the fused full kernel (the churn
# signature: every valset rotation shows up as full->fill->hit over the
# next batches).
_m_predecomp = telemetry.counter(
    "verifier_predecomp_batches_total",
    "Device batches through the ed25519 predecompressed-pubkey cache, "
    "by outcome", ("outcome",))
_m_predecomp_evictions = telemetry.counter(
    "verifier_predecomp_evictions_total",
    "Per-pubkey rows put out of the ed25519 key table, least recently "
    "used first (valset churn beyond its capacity)")
_m_predecomp_keys = telemetry.gauge(
    "verifier_predecomp_keys",
    "Pubkey rows currently resident in the ed25519 key table")
_m_predecomp_assembled = telemetry.counter(
    "verifier_predecomp_assembled_total",
    "Device batches that got predecompressed rows, by whether their "
    "slots in the key table were built (resolved by the table's "
    "lookup, after a fill too) or reused from the memo of whole key "
    "sequences", ("how",))
_m_predecomp_lanes = telemetry.counter(
    "verifier_predecomp_lanes_total",
    "Lanes the key table's lookup resolved, by what settled them: "
    "index (the sorted key prefixes and one compare of all 32 bytes) "
    "or second (the lane's prefix is resident under another key, so "
    "the keys that share it were walked)", ("how",))
_m_batch_sigs = telemetry.counter(
    "verifier_batch_sigs_total",
    "Signatures dispatched to the device, by the form their batch "
    "arrived in: columns (a SigColumns, prepared in place) or items "
    "(triples, walked one by one)", ("form",))
_m_prep_lanes = telemetry.counter(
    "verifier_prep_lanes_total",
    "Lanes of natively prepared device batches, by where their "
    "SHA-512 ran: sharded (the batch cut over several threads of the "
    "native prep) or inline (the caller's thread alone)", ("how",))
# the verifier's request id in the span recorder: every span of one
# dispatch (telemetry/trace.py) carries its number as `req`
_dispatch_seq = itertools.count(1)

# Per-dispatch chunk. The fused pallas kernel tiles batches internally
# (512/VMEM tile), so big dispatches amortize launch overhead. 8192
# came from a sweep on an earlier host; not re-measured on the attached
# chip.
BATCH_CHUNK = 8192

# Threads that fetch a multi-chunk batch's verdict arrays at once.
# Whether that beats a serial fetch, and at how many workers, is not
# measured on the attached chip either.
FETCH_WORKERS = 8

# The native prep's SHA-512 loop runs on several threads once a batch
# gives each this many lanes, and on no more than the cap. Both from
# scripts/prep_threads.py on the chip's host (PERF.md section 6, PR 36).
MIN_LANES_A_THREAD = 2048
MAX_PREP_THREADS = 8


@functools.cache
def _usable_cores() -> int:
    """Cores this process may run on, read once."""
    return len(os.sched_getaffinity(0))


def prep_threads(n: int, cores: int | None = None) -> int:
    """Threads for the native prep of an n-lane batch: from the lanes
    and the cores the process may run on (one is left to the rest of
    the process), never from a knob. 1 = the caller's thread alone,
    which is what a small batch and a one-core host get."""
    if cores is None:
        cores = _usable_cores()
    return max(1, min(cores - 1, n // MIN_LANES_A_THREAD,
                      MAX_PREP_THREADS))


_pool_lock = threading.Lock()

# shared by every verifier of the process, started at the first
# multi-chunk fetch
_fetch_pool = None


def _fetch_pool_get():
    global _fetch_pool
    with _pool_lock:
        if _fetch_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            _fetch_pool = ThreadPoolExecutor(
                max_workers=FETCH_WORKERS,
                thread_name_prefix="tm-verify-fetch")
        return _fetch_pool


# 'auto' | 'off' | power-of-two int, validated eagerly (shared with the
# ops.merkle mesh dispatch — one spec grammar for the whole device plane)
_parse_mesh_spec = _pmesh.parse_mesh_spec


def _resolved_by_its_caller(dispatch, items: list):
    """Resolver of a call no device would ever see: `dispatch(items)`
    runs on the thread that first asks for the verdicts, which are kept
    for one that asks again, and an exception is that caller's own. Two
    threads that ask at once both verify, to the same verdicts."""
    value = None

    def resolve() -> np.ndarray:
        nonlocal value
        if value is None:
            value = np.asarray(dispatch(items)())
        return value

    return resolve


class BatchVerifier:
    def __init__(self, backend: str = "auto", auto_threshold: int = None,
                 mesh: str = "off"):
        # auto_threshold: batches at or below this verify scalar on host
        # (OpenSSL). Where the scalar/batch breakeven lies depends on
        # the dispatch round trip, which is not measured on the
        # attached chip (ROADMAP Queue 1, "Live consensus never reaches
        # the device"); the default of 128 keeps small interactive
        # commits off it, deployments tune it with
        # TM_TPU_AUTO_THRESHOLD. Bulk paths (fast-sync windows, lite
        # chains, 1000+-validator commits) sit far above any setting.
        if auto_threshold is None:
            auto_threshold = knobs.knob_int("TM_TPU_AUTO_THRESHOLD",
                                            default=128)
        # eager, loud validation — this is fed by config/env text, and a
        # typo must fail at startup (asserts vanish under python -O)
        if backend not in ("auto", "jax", "python"):
            raise ValueError(
                f"verifier backend must be auto|jax|python, got {backend!r}")
        self.backend = backend
        self.auto_threshold = auto_threshold
        self.mesh = _parse_mesh_spec(mesh)
        self.mesh_devices = 0          # >0 once batches are sharded
        self._mesh = None              # the jax Mesh, once resolved
        self._mesh_resolved = self.mesh == "off"
        self._resolve_lock = threading.Lock()
        # stats mutations are read-modify-writes reached from every
        # reactor/RPC thread concurrently — one lock, held for dict
        # arithmetic only (never across a dispatch)
        self._stats_lock = threading.Lock()
        #: guarded_by _stats_lock
        self.stats = {"calls": 0, "sigs": 0, "jax_sigs": 0}

    def _resolve_mesh(self) -> None:
        """Build the mesh on first device dispatch. mesh='auto' uses the
        largest power-of-two device count (shard_map needs the padded
        batch axis divisible by the mesh; buckets are powers of two);
        single-device hosts dispatch unsharded. A host with no usable
        backend raises here. Thread-safe: concurrent verify() calls
        (reactor windows, evidence, RPC) must not dispatch with a
        half-initialized mesh."""
        with self._resolve_lock:
            if self._mesh_resolved:
                return
            import jax
            # explicit N > available raises RuntimeError (loud, and not
            # a bad-peer-data signal) before _mesh_resolved flips
            n = _pmesh.resolve_mesh_size(self.mesh, len(jax.devices()))
            if n >= 2:
                self._mesh = _pmesh.make_mesh(n)
                self.mesh_devices = n
            if telemetry.enabled():
                _m_mesh_devices.set(self.mesh_devices)
            self._mesh_resolved = True

    def verify(self, items: Sequence[tuple[bytes, bytes, bytes]]) -> np.ndarray:
        """items: (pubkey32, message, signature64) triples -> bool[N]."""
        return self.verify_async(items)()

    def verify_async(self, items: Sequence[tuple[bytes, bytes, bytes]]):
        """Dispatch without blocking: returns a zero-arg resolver that
        materializes bool[N]. jax dispatch is asynchronous, so the
        caller can overlap device compute with host work (the pipelined
        fast-sync loop applies window k-1 while window k verifies
        on-device); every chunk is enqueued up front.

        A call of 1 to auto_threshold signatures under 'auto' or
        'python' is the host's, signature by signature, wherever it
        runs: a live vote, a proposal or a small set's LastCommit stays
        on its caller's thread, verified when its resolver is first
        called (VoteSet and ValidatorSet.verify_commit_async dispatch
        and resolve at different points, and the work belongs to the
        second). Under 'jax' every call is the device's and is enqueued
        here, as is every call above the threshold."""
        if self.backend != "jax" and 0 < len(items) <= self.auto_threshold:
            return _resolved_by_its_caller(self._verify_async_direct,
                                           list(items))
        return self._verify_async_direct(items)

    def _verify_async_direct(self, items):
        """Count the call, open its `verify.dispatch` span and route
        it; never re-enters verify_async."""
        n = len(items)
        with self._stats_lock:
            self.stats["calls"] += 1
            self.stats["sigs"] += n
        if n == 0:
            out0 = np.zeros(0, np.bool_)
            return lambda: out0
        req = next(_dispatch_seq) if telemetry.enabled() else None
        with trace.span("verify.dispatch", req=req, n=n,
                        backend=self.backend) as span:
            return self._dispatch_direct(items, n, span)

    def _dispatch_direct(self, items, n: int, span):
        """_verify_async_direct's body, inside its `verify.dispatch`
        span; the resolver it returns names that span as its cause."""
        t_dispatch = time.perf_counter()
        _m_batch_size.observe(n)
        use_jax = self.backend == "jax" or (
            self.backend == "auto" and n > self.auto_threshold)
        if not use_jax:
            # scalar host path, routed by key type (ed25519 |
            # secp256k1); batches big enough to amortize per-key
            # precompute use the table oracle (keys.verify_many)
            from tendermint_tpu.types.keys import verify_many
            out1 = np.array(verify_many(items), np.bool_)
            if telemetry.enabled():
                _m_calls.labels("python").inc()
                _m_sigs.labels("python").inc(n)
                _m_dispatch.labels("python").observe(
                    time.perf_counter() - t_dispatch)
            return lambda: out1
        # the whole host prep (classification, length/s<L checks,
        # SHA-512 + mod-L) in one native call, GIL released. A batch
        # that arrives as columns is prepared from them in place; any
        # other Sequence, a SigColumns too, is walked as triples. What
        # the native prep declines (secp256k1 keys, non-bytes members,
        # native unavailable) is split by key type below or, all
        # ed25519, prepared by ops/ed25519's own host prep.
        from tendermint_tpu import native
        with trace.span("verify.prep", n=n):
            prep, form = None, "columns"
            threads = prep_threads(n)
            if isinstance(items, SigColumns):
                prep = native.prep_columns(items.pk, items.sigs,
                                           items.msgs, items.idx, threads)
            if prep is None:
                prep, form = native.prep_items(items, threads), "items"
            if prep is None:
                # 33-byte compressed-SEC1 pubkeys are secp256k1
                secp_idx = [i for i, it in enumerate(items)
                            if len(it[0]) == 33 and it[0][0] in (2, 3)]
                if not secp_idx:
                    from tendermint_tpu.ops import ed25519
                    prep = ed25519.prepare_batch_bytes(
                        [it[0] for it in items], [it[1] for it in items],
                        [it[2] for it in items])
            elif telemetry.enabled():
                _m_prep_lanes.labels(
                    "sharded" if threads > 1 else "inline").inc(n)
        if prep is None:
            # outside the prep span: the ed25519 lanes open a dispatch
            # of their own
            return self._dispatch_mixed(items, n, secp_idx)
        from tendermint_tpu.ops import ed25519
        if not self._mesh_resolved:
            self._resolve_mesh()
        self._record_jax_dispatch(n, form)
        pk, rb, sb, hb, pre = prep
        pending = []
        occ = telemetry.enabled()
        t_enqueued = 0.0
        for lo in range(0, n, BATCH_CHUNK):
            hi = min(lo + BATCH_CHUNK, n)
            res = ed25519.verify_prepared_async(
                pk[lo:hi], rb[lo:hi], sb[lo:hi], hb[lo:hi],
                mesh=self._mesh)
            if occ and not t_enqueued:
                t_enqueued = time.perf_counter()
            pending.append((lo, hi, res, pre[lo:hi]))
            if occ:
                b = ed25519._bucket(
                    hi - lo, min_size=max(8, self.mesh_devices))
                _m_occupancy.observe((hi - lo) / b)
                if self.mesh_devices >= 2:
                    _pmesh.record_dispatch("verify", hi - lo, b)
        return self._make_resolver(n, pending, t_dispatch, span, t_enqueued)

    def _dispatch_mixed(self, items, n: int, secp_idx: list):
        """Mixed-key routing: the secp256k1 lanes are verified on host
        (off the TPU hot path by design, types/keys.py); everything
        else goes to the ed25519 device batch, where a non-ed25519 key
        fails its precheck anyway."""
        from tendermint_tpu.types.keys import verify_any
        secp_ok = {i: verify_any(*items[i]) for i in secp_idx}
        ed_items = [it for i, it in enumerate(items)
                    if i not in secp_ok]
        if not ed_items:
            out2 = np.zeros(n, np.bool_)
            for i, ok in secp_ok.items():
                out2[i] = ok
            return lambda: out2
        inner = self._verify_async_direct(ed_items)
        with self._stats_lock:
            self.stats["calls"] -= 1  # the outer call already counted
            self.stats["sigs"] -= len(ed_items)

        def resolve_mixed() -> np.ndarray:
            ed_ok = inner()
            out3 = np.zeros(n, np.bool_)
            k = 0
            for i in range(n):
                if i in secp_ok:
                    out3[i] = secp_ok[i]
                else:
                    out3[i] = ed_ok[k]
                    k += 1
            return out3

        return resolve_mixed

    def _record_jax_dispatch(self, n: int, form: str) -> None:
        """Stats + calls/sigs samples for one device dispatch (chunk
        occupancy is observed inside the chunk loop, where lo/hi and
        the ed25519 module are already in hand)."""
        with self._stats_lock:
            self.stats["jax_sigs"] += n
        if not telemetry.enabled():
            return
        _m_calls.labels("jax").inc()
        _m_sigs.labels("jax").inc(n)
        _m_batch_sigs.labels(form).inc(n)

    @staticmethod
    def _make_resolver(n: int, pending, t_dispatch: float, span,
                       t_enqueued: float):
        """`span` is the dispatch's `verify.dispatch`: the fetch, on
        whatever thread resolves, names it as its cause and shares its
        request id; `t_enqueued` is when the first chunk was enqueued,
        from where the device has had work of this dispatch."""
        cause, req = span.id, span.req

        def resolve() -> np.ndarray:
            out = np.zeros(n, np.bool_)
            with trace.span("verify.fetch", req=req, cause=cause,
                            chunks=len(pending)):
                if len(pending) > 1:
                    arrs = list(_fetch_pool_get().map(
                        lambda p: np.asarray(p[2]), pending))
                else:
                    arrs = [np.asarray(pending[0][2])]
            t_fetched = time.perf_counter() if telemetry.enabled() else 0.0
            if t_fetched and t_enqueued:
                trace.complete("verify.inflight", t_enqueued, t_fetched,
                               req=req, cause=cause)
            for (lo, hi, _res, pre), arr in zip(pending, arrs):
                out[lo:hi] = arr[:hi - lo] & pre
            if t_fetched:
                _m_dispatch.labels("jax").observe(
                    time.perf_counter() - t_dispatch)
            return out

        return resolve

    def verify_one(self, pubkey: bytes, msg: bytes, sig: bytes) -> bool:
        return bool(self.verify([(pubkey, msg, sig)])[0])


_default: BatchVerifier | None = None


def default_verifier() -> BatchVerifier:
    """Process-wide verifier; backend from TM_TPU_VERIFIER (auto|jax|python),
    mesh from TM_TPU_MESH (auto|off|N, default auto — a node on a
    multi-device host shards its signature batches over every chip with
    zero code changes)."""
    global _default
    if _default is None:
        _default = BatchVerifier(
            knobs.knob_str("TM_TPU_VERIFIER", default="auto"),
            mesh=knobs.knob_str("TM_TPU_MESH", default="auto"))
    return _default


def set_default_verifier(v: BatchVerifier) -> None:
    global _default
    _default = v
