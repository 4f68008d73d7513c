"""Batched Ed25519 verification — the flagship TPU kernel.

Replaces the reference's scalar one-verify-per-call hot loops
(types/validator_set.go:240-265 VerifyCommit, types/vote_set.go:189 vote
ingestion, blockchain/reactor.go:286 fast-sync) with a single
fixed-shape batch:

    verify_batch(pubkeys[N,32], sig_R[N,32], s_bits[N,256], h_bits[N,256])
        -> bool[N]

Work split (SURVEY.md §7 "hard parts"):
  host  — SHA-512 of (R || A || msg) over variable-length messages, scalar
          reduction mod L, s < L malleability check. Cheap (µs/sig) and
          inherently variable-shape.
  TPU   — point decompression (field sqrt) and the double-scalar
          multiplication s*B - h*A (the ~99% of the cost), batched over N
          with complete-addition Edwards arithmetic. Verdict: compare the
          canonical encoding of the result against sig_R (cofactorless,
          matching the Go x/crypto semantics the reference uses).

The reference kernel is pure jnp over int32, so it jit-compiles for any
batch shape; on a TPU the fused Pallas kernels (ops/ladder_pallas.py)
take every batch that fills their 512-row tile. Both shard over a device
mesh by sharding the leading axis (parallel/mesh.batch_sharded). Which
kernel served each dispatch is counted in predecomp_stats().
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu import telemetry
from tendermint_tpu.ops import curve
from tendermint_tpu.ops import field as fe
from tendermint_tpu.telemetry import trace

L_ORDER = (1 << 252) + 27742317777372353535851937790883648493

# counted where the bytes leave the host (_dispatch), beside the
# `verify.enqueue` span
_m_h2d_bytes = telemetry.counter(
    "verifier_h2d_bytes_total",
    "Bytes of host arrays handed to the device by verify dispatches")


# ---------------------------------------------------------------------------
# Host-side preparation
# ---------------------------------------------------------------------------

def _bits_le(values: np.ndarray) -> np.ndarray:
    """uint8[N,32] little-endian scalar bytes -> int32[N,256] LE bits."""
    return np.unpackbits(values, axis=-1, bitorder="little").astype(np.int32)


def prepare_batch_bytes(pubkeys, msgs, sigs):
    """Host prep, PACKED form: (pubkeys u8[N,32], R u8[N,32],
    s u8[N,32], h u8[N,32], precheck bool[N]).

    The packed scalars are what crosses the host->device boundary (32
    bytes each); bit/digit unpacking happens ON DEVICE — shipping
    pre-unpacked i32[N,256] bit arrays costs 64x the transfer bytes.

    precheck is False for malformed inputs (bad lengths, s >= L); such
    entries still flow through the kernel with zeroed scalars so the
    batch shape stays static.

    When every pubkey/sig has the canonical length, the whole batch is
    prepared by ONE call into the native hostops (SHA-512 + mod-L in
    C++, native/hostops.cpp tm_ed25519_prepare) — the per-signature
    Python loop below is the fallback and the malformed-input path."""
    n = len(pubkeys)
    pk_list = [bytes(p) for p in pubkeys]
    sg_list = [bytes(s) for s in sigs]
    if n > 0 and all(len(p) == 32 for p in pk_list) and \
            all(len(s) == 64 for s in sg_list):
        from tendermint_tpu import native
        pk_cat = b"".join(pk_list)
        sg_cat = b"".join(sg_list)
        out = native.ed25519_prepare(pk_cat, sg_cat,
                                     [bytes(m) for m in msgs])
        if out is not None:
            h_bytes, pre = out
            sg = np.frombuffer(sg_cat, np.uint8).reshape(n, 64)
            pk = np.frombuffer(pk_cat, np.uint8).reshape(n, 32).copy()
            rb = sg[:, :32].copy()
            s_bytes = np.where(pre[:, None], sg[:, 32:], 0).astype(np.uint8)
            pk[~pre] = 0
            rb[~pre] = 0
            return pk, rb, s_bytes, h_bytes, pre
    pk = np.zeros((n, 32), np.uint8)
    rb = np.zeros((n, 32), np.uint8)
    s_bytes = np.zeros((n, 32), np.uint8)
    h_bytes = np.zeros((n, 32), np.uint8)
    pre = np.zeros(n, np.bool_)
    for i in range(n):
        p, m, sg = bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i])
        if len(p) != 32 or len(sg) != 64:
            continue
        s = int.from_bytes(sg[32:], "little")
        if s >= L_ORDER:
            continue
        h = int.from_bytes(
            hashlib.sha512(sg[:32] + p + m).digest(), "little") % L_ORDER
        pk[i] = np.frombuffer(p, np.uint8)
        rb[i] = np.frombuffer(sg[:32], np.uint8)
        s_bytes[i] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
        h_bytes[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
        pre[i] = True
    return pk, rb, s_bytes, h_bytes, pre


def prepare_batch(pubkeys, msgs, sigs):
    """Legacy unpacked form: (..., s_bits i32[N,256], h_bits i32[N,256],
    precheck). Prefer prepare_batch_bytes + the *_from_bytes kernels."""
    pk, rb, s_bytes, h_bytes, pre = prepare_batch_bytes(pubkeys, msgs, sigs)
    return pk, rb, _bits_le(s_bytes), _bits_le(h_bytes), pre


def bits_from_bytes_dev(b_u8):
    """Device-side unpack: uint8[..., 32] -> int32[..., 256] LE bits."""
    b = b_u8.astype(jnp.int32)
    bits = (b[..., :, None] >> jnp.arange(8, dtype=jnp.int32)) & 1
    return bits.reshape(b.shape[:-1] + (256,))


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------

def verify_kernel(pubkeys_u8, sig_r_u8, s_bits, h_bits):
    """Pure device function: bool[...] verdicts.

    pubkeys_u8, sig_r_u8: uint8[..., 32]; s_bits, h_bits: int32[..., 256].
    """
    A, ok_a = curve.decompress(pubkeys_u8)
    A_neg = curve.negate(A)
    # Zero the scalars of invalid pubkeys so the ladder math stays benign.
    s_bits = jnp.where(ok_a[..., None], s_bits, 0)
    h_bits = jnp.where(ok_a[..., None], h_bits, 0)
    Q = curve.scalar_mult_straus_w4(s_bits, h_bits, A_neg)
    enc = curve.encode(Q)
    match = jnp.all(enc == sig_r_u8, axis=-1)
    return ok_a & match


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    """Platform of the default backend, asked once per process. A host
    with no usable backend raises here, at the first device dispatch."""
    return jax.devices()[0].platform


def _pallas_available() -> bool:
    """The fused Mosaic kernels serve every TPU backend and nothing
    else. The platform alone decides: a TPU on which the Pallas module
    does not import or compile raises at the dispatch and is never
    demoted to the jnp ladder."""
    return _platform() == "tpu"


@jax.jit
def _verify_from_bytes_jnp(pk, rb, s_bytes, h_bytes):
    return verify_kernel(pk, rb, bits_from_bytes_dev(s_bytes),
                         bits_from_bytes_dev(h_bytes))


@jax.jit
def _verify_from_bytes_pallas(pk, rb, s_bytes, h_bytes):
    from tendermint_tpu.ops import ladder_pallas
    return ladder_pallas.verify_pallas(
        pk, rb, bits_from_bytes_dev(s_bytes),
        bits_from_bytes_dev(h_bytes))


def _dispatch(variant: str, mesh, *args):
    """Hand one padded batch (host arrays) to the device and enqueue
    it on the kernel its shape selects, counted; the transfers and the
    jitted call are the `verify.enqueue` span.
    variant: 'full' | 'pre'. The fused Pallas
    kernel takes every batch whose per-device rows fill its 512 tile
    on a TPU; everything else (CPU backends, interactive sizes where
    kernel choice barely matters) takes the jnp ladder. With a mesh
    the same choice is made for the per-shard body."""
    rows = args[0].shape[0]
    ndev = 1 if mesh is None else mesh.devices.size
    local = rows // ndev
    if _pallas_available() and local >= 512 and local % 512 == 0:
        fn, name = ((_verify_pre_pallas, "pallas_pre") if variant == "pre"
                    else (_verify_from_bytes_pallas, "pallas_full"))
    else:
        fn, name = ((_verify_pre_jnp, "jnp_pre") if variant == "pre"
                    else (_verify_from_bytes_jnp, "jnp_full"))
    shape = f"{name}[{rows}]"
    if mesh is not None:
        from tendermint_tpu.parallel import mesh as pmesh
        fn = pmesh.batch_sharded(fn, mesh)
        shape = f"{name}[{rows}/{ndev}]"
        if name.startswith("jnp_"):
            name = "mesh_jnp"
    with trace.span("verify.enqueue", kernel=name, rows=rows):
        if telemetry.enabled():
            _m_h2d_bytes.inc(sum(
                a.nbytes for a in args if isinstance(a, np.ndarray)))
        on_device = [jnp.asarray(a) for a in args]
        t0 = time.perf_counter()
        out = fn(*on_device)
        _note_kernel(name, shape, time.perf_counter() - t0)
    return out


# ---------------------------------------------------------------------------
# Pre-decompressed pubkey cache (stable-valset fast path)
# ---------------------------------------------------------------------------
# Point decompression is a field sqrt — a ~250-multiply exponentiation,
# a significant slice of the verify kernel — yet consensus workloads
# verify the SAME validator set's keys over and over (every commit,
# every fast-sync window, every lite header). The cache keys PER
# 32-BYTE PUBKEY, so once a validator's key has been decompressed once,
# EVERY later batch containing it hits, whatever the batch's
# composition or order.
# A key's row is the canonical field bytes of (-A).x and A.y plus the
# validity flag: 65 bytes, (-A).x | A.y | ok.
#
# The rows live in ONE table (_KeyTable: arrays of _PREDECOMP_MAX_KEYS
# slots) and a batch gets its rows BY INDEX: the lookup turns the
# batch's keys u8[m,32] into slots int32[m] with array operations alone
# (no Python statement runs once a lane: stacking 8,192 rows from a
# dict of tuples cost 12.7 ms a chunk on the chip's host, a third of a
# follower's pass; the lookup costs the same whether the batch's key
# sequence is new or not). The table keeps a mirror of its rows on the
# device, replaced whole after a fill, and the *_pre programs gather
# rows[idx] there: a dispatch sends 4 bytes a lane of indices where it
# sent the 65 of the rows. With a mesh the rows are taken on the host,
# before the batch axis is split: the table is never sharded.
#
# _predecomp_memo keeps the resolved indices of the last few batches
# under their whole key bytes (a commit's keys arrive in validator-set
# order every time), so a key sequence seen before costs one dict
# lookup. The table stays the source of truth: an entry is used only
# while no slot has changed its key since it was resolved
# (_KeyTable.epoch), and a use stamps its slots and counts as the `hit`
# it is.

_PREDECOMP_MAX_KEYS = 16384  # slots, 1.7 MB — covers a 10k-validator set
# batches below this padded size skip the cache: one-shot small batches
# must not pay the extra decompress dispatch (tests lower it to drive
# the cache logic on already-compiled small shapes)
_PREDECOMP_MIN_BATCH = 64
# a padded batch's key bytes -> (the table's epoch when resolved, its
# slots int32[m], read-only). Hold 8, least recently used out (256 KB of
# indices at most, and the keys' 2 MB): a chain of ONE set repeats 4
# sequences (99.0% of chunks reused); where the key list moves every 63
# headers a pass is 50 new ones and none is reused (PERF.md, PR 40),
# which is fine: resolving a sequence anew costs about what keeping it
# saves. Not sized to any benchmark's passes: a real follower sees a
# chunk once.
_PREDECOMP_MEMO_MAX = 8
_predecomp_memo: "OrderedDict[bytes, tuple]" = OrderedDict()
# pubkeys sighted once (a first sighting of keys that each fill one lane
# stays on the fused full kernel: a one-shot batch must not pay for
# decompressing them apart; a window's chunk, which shows each key in
# many lanes, fills at once, so a chain's sync compiles no full kernel)
_predecomp_seen: "OrderedDict[bytes, bool]" = OrderedDict()


class _KeyTable:
    """The predecompressed rows of up to _PREDECOMP_MAX_KEYS pubkeys
    (the value when the table was last cleared), a slot each: `keys`
    u8[cap,32], `rows` u8[cap,65] ((-A).x | A.y | ok) and `stamp`, the
    tick of the slot's last use. Slots 0..used-1 hold a key. The index
    is the keys' first eight bytes as u64, sorted, beside the slot of
    each: a prefix PROPOSES a slot and all 32 bytes decide. Not
    thread-safe: every caller holds _predecomp_lock."""

    def __init__(self):
        self.epoch = 0
        self.clear()

    def clear(self) -> None:
        cap = _PREDECOMP_MAX_KEYS
        self.keys = np.zeros((cap, 32), np.uint8)
        self._keys64 = self.keys.view(np.uint64)        # [cap,4], shared
        self.rows = np.zeros((cap, 65), np.uint8)
        self.stamp = np.zeros(cap, np.int64)
        self.used = 0
        self.tick = 0
        # counts the times a slot's key changed under the indices handed
        # out before: what resolved indices are valid against
        self.epoch += 1
        self._prefix = np.zeros(0, np.uint64)   # sorted
        self._slot = np.zeros(0, np.int32)      # _prefix[i]'s slot
        self._mirror = None

    def __len__(self) -> int:
        return self.used

    def lookup(self, pk: np.ndarray):
        """pk u8[m,32] -> (slot int32[m], miss bool[m], lanes that
        needed the second step). Exact: a lane resolves to a slot only
        if all 32 bytes equal the slot's key; a missed lane's slot is
        meaningless. The second step walks on through the resident keys
        that share a lane's prefix, one array operation a key of the
        longest such run (keys are uniform in their low bytes: a run of
        two takes 2**32 tries to make)."""
        m = pk.shape[0]
        if not self.used:
            return np.zeros(m, np.int32), np.ones(m, np.bool_), 0
        pk64 = np.ascontiguousarray(pk).view(np.uint64)     # [m,4]
        prefix = pk64[:, 0]
        pos = np.searchsorted(self._prefix, prefix)
        np.minimum(pos, self.used - 1, out=pos)
        slot = self._slot[pos]
        found = (self._keys64[slot] == pk64).all(axis=1)
        # the prefix is resident under another key: its neighbours next
        todo = np.flatnonzero(~found & (self._prefix[pos] == prefix))
        second = todo.size
        while todo.size:
            nxt = pos[todo] + 1
            on = nxt < self.used
            todo, nxt = todo[on], nxt[on]
            on = self._prefix[nxt] == prefix[todo]
            todo, nxt = todo[on], nxt[on]
            pos[todo] = nxt
            hit = (self._keys64[self._slot[nxt]] == pk64[todo]).all(axis=1)
            slot[todo[hit]] = self._slot[nxt[hit]]
            found[todo[hit]] = True
            todo = todo[~hit]
        return slot, ~found, second

    def touch(self, slots: np.ndarray) -> None:
        """One use of `slots`: they are the most recently used now."""
        self.tick += 1
        self.stamp[slots] = self.tick

    def insert(self, keys, xneg, y, ok) -> int:
        """Store the rows of k distinct keys, none resident, k at most
        the slots that are free or stamped before the current tick: into
        the free slots first, then over the least recently stamped.
        Returns how many keys that put out."""
        k = keys.shape[0]
        free = min(k, self.keys.shape[0] - self.used)
        slots = np.arange(self.used, self.used + free)
        evicted = k - free
        if evicted:
            victims = np.argsort(self.stamp[:self.used],
                                 kind="stable")[:evicted]
            slots = np.concatenate([victims, slots])
            self.epoch += 1
        self.keys[slots] = keys
        self.rows[slots, :32] = xneg
        self.rows[slots, 32:64] = y
        self.rows[slots, 64] = ok
        self.stamp[slots] = self.tick
        self.used += free
        order = np.argsort(self._keys64[:self.used, 0], kind="stable")
        self._prefix = self._keys64[:self.used, 0][order]
        self._slot = order.astype(np.int32)
        self._mirror = None
        return evicted

    def mirror(self):
        """`rows` on the device, as of now: a copy, made at the first
        dispatch after a fill and immutable, so a dispatch in flight
        keeps the table it was resolved against."""
        if self._mirror is None:
            if telemetry.enabled():
                _m_h2d_bytes.inc(self.rows.nbytes)
            self._mirror = jnp.array(self.rows)
        return self._mirror


_predecomp = _KeyTable()
# hit   = batch fully served from the table's rows (pre kernel, no sqrt)
# fill  = repeat-traffic batch decompressed once + rows stored
# full  = mostly-unseen batch routed to the fused full kernel
# evict = per-pubkey rows dropped, least recently used first (valset
#         churn beyond capacity — invisible before this counter: a
#         rotating valset quietly degraded every "hit" into a re-fill)
#
# The same dict counts every device dispatch by the kernel that served
# it (a sharded dispatch whose per-shard body is the jnp ladder counts
# as mesh_jnp, sign_scalar counts host-signed batches; `decompress`
# stays 0 since a fill decompresses on the host, and stays a key for
# the readers that ask for it), and keeps under
# first_call_s, per "program[rows]" or "program[rows/devices]", the
# seconds the FIRST dispatch of that shape spent inside the jit call:
# trace + lower + compile, or the persistent-cache load. Later
# dispatches of a shape only enqueue.
_predecomp_stats = {"hit": 0, "fill": 0, "full": 0, "evict": 0,
                    "pallas_full": 0, "pallas_pre": 0, "jnp_full": 0,
                    "jnp_pre": 0, "mesh_jnp": 0, "decompress": 0,
                    "sign_pallas": 0, "sign_scalar": 0,
                    "first_call_s": {}}


def _predecomp_note(outcome: str, n: int = 1, how: str = "") -> None:
    """Mirror a cache outcome into tm_verifier_predecomp_* telemetry
    (registered by models/verifier beside the other tm_verifier_*
    families; lazy import — models.verifier is loaded in any process
    that dispatches batches here). `how` says, for a batch that gets
    rows, whether its slots were "built" (resolved by the table's
    lookup) or "reused" (kept by the memo under its key sequence)."""
    _predecomp_stats[outcome] += n
    from tendermint_tpu.models import verifier
    if outcome == "evict":
        verifier._m_predecomp_evictions.inc(n)
    else:
        verifier._m_predecomp.labels(outcome).inc(n)
    if how:
        verifier._m_predecomp_assembled.labels(how).inc()
    verifier._m_predecomp_keys.set(len(_predecomp))


def _predecomp_note_lanes(index: int, second: int) -> None:
    """Lanes one lookup of the key table settled, by how (see
    _KeyTable.lookup), into tm_verifier_predecomp_lanes_total."""
    from tendermint_tpu.models import verifier
    verifier._m_predecomp_lanes.labels("index").inc(index)
    if second:
        verifier._m_predecomp_lanes.labels("second").inc(second)


# Batched verifies dispatch concurrently (fast-sync collector, lite
# certify, RPC handlers all share default_verifier()). One lock guards
# the table, the memo and the sighted keys: a batch's slots and the
# mirror they index are taken under ONE hold of it.
_predecomp_lock = threading.Lock()


def _note_kernel(name: str, shape: str = "", secs: float = 0.0) -> None:
    with _predecomp_lock:
        _predecomp_stats[name] += 1
        if shape:
            _predecomp_stats["first_call_s"].setdefault(
                shape, round(secs, 3))


def predecomp_stats() -> dict:
    """Snapshot of the device-plane counters (bench/report surface):
    hit/fill/full batch outcomes of the cache, row evictions, resident
    keys, the batch hit rate, dispatches by kernel and each shape's
    first-call seconds."""
    with _predecomp_lock:
        s = dict(_predecomp_stats)
        s["first_call_s"] = dict(s["first_call_s"])
        s["keys"] = len(_predecomp)
    routed = s["hit"] + s["fill"] + s["full"]
    s["hit_rate"] = round(s["hit"] / routed, 4) if routed else 0.0
    return s


@jax.jit
def _decompress_to_bytes(pk_u8):
    """(-A).x and A.y as canonical field bytes + validity mask (inputs
    to the *_pre kernels), by the device's own field arithmetic: the
    statement that _decompress_keys is held to, row for row."""
    (x, y, _one, _t), ok = curve.decompress(pk_u8)
    return fe.to_bytes(fe.neg(x)), fe.to_bytes(y), ok


def _decompress_keys(keys: np.ndarray):
    """keys u8[k,32] -> ((-A).x u8[k,32], A.y u8[k,32], ok bool[k]):
    what _decompress_to_bytes gives, bit for bit (points and
    non-points, y >= p, x = 0 under the sign bit), in Python integers
    on the host, a seventh of a millisecond a key. A fill decompresses
    the keys it stores and nothing else, and with it a batch can fill
    at its first sighting (_predecomp_rows): as a device program the
    decompression took a whole padded batch, a dispatch and a fetch,
    and a compile of two seconds a batch shape."""
    p, d, sqrt_m1 = fe.P, fe.D_INT, fe.SQRT_M1_INT
    low = (1 << 255) - 1
    xneg, ys, oks = [], [], []
    for raw in map(bytes, keys):
        n = int.from_bytes(raw, "little")
        sign, y = n >> 255, (n & low) % p
        y2 = y * y % p
        u, v = (y2 - 1) % p, (y2 * d + 1) % p
        v3 = v * v % p * v % p
        x = u * v3 % p * pow(u * v3 % p * v3 % p * v % p,
                             (p - 5) // 8, p) % p
        check = v * x % p * x % p
        flipped = check == (p - u) % p
        if flipped:
            x = x * sqrt_m1 % p
        ok = (check == u or flipped) and not (x == 0 and sign == 1)
        if x & 1 != sign:
            x = (p - x) % p
        xneg.append(((p - x) % p).to_bytes(32, "little"))
        ys.append(y.to_bytes(32, "little"))
        oks.append(ok)
    as_rows = lambda rows: np.frombuffer(     # noqa: E731
        b"".join(rows), np.uint8).reshape(-1, 32)
    return as_rows(xneg), as_rows(ys), np.array(oks, np.bool_)


def _rows_at(rows, idx):
    """(xneg bytes, y bytes, ok) of a batch: `rows` u8[m,65] as the
    table packs them or, with `idx`, the table's mirror gathered at the
    batch's slots (they come from _KeyTable.lookup, so they are in
    bounds)."""
    if idx is not None:
        rows = rows.at[idx].get(mode="promise_in_bounds")
    return rows[:, :32], rows[:, 32:64], rows[:, 64] != 0


@jax.jit
def _verify_pre_jnp(rb, s_bytes, h_bytes, rows, idx=None):
    xnb, yb, ok = _rows_at(rows, idx)
    s_bits = bits_from_bytes_dev(s_bytes)
    h_bits = bits_from_bytes_dev(h_bytes)
    xn, _ = fe.from_bytes(xnb)
    y, _ = fe.from_bytes(yb)
    one = jnp.broadcast_to(jnp.asarray(fe.ONE), y.shape)
    A_neg = (xn, y, one, fe.mul(xn, y))
    s_bits = jnp.where(ok[..., None], s_bits, 0)
    h_bits = jnp.where(ok[..., None], h_bits, 0)
    Q = curve.scalar_mult_straus_w4(s_bits, h_bits, A_neg)
    enc = curve.encode(Q)
    return ok & jnp.all(enc == rb, axis=-1)


@jax.jit
def _verify_pre_pallas(rb, s_bytes, h_bytes, rows, idx=None):
    from tendermint_tpu.ops import ladder_pallas
    xnb, yb, ok = _rows_at(rows, idx)
    return ladder_pallas.verify_pallas_pre(
        xnb, yb, ok, rb, bits_from_bytes_dev(s_bytes),
        bits_from_bytes_dev(h_bytes))


def _verify_cached_predecomp(pk_np, rb, s_bytes, h_bytes, mesh=None):
    """Returns verdicts via the predecompressed path, or None when this
    batch takes the fused full kernel (_predecomp_rows says which)."""
    with trace.span("verify.predecomp", rows=pk_np.shape[0]):
        handed = _predecomp_rows(pk_np, mesh)
    if handed is None:
        return None
    return _dispatch("pre", mesh, rb, s_bytes, h_bytes, *handed)


def _table_rows(idx, mesh):
    """What a `pre` program is handed, after R, s and h, for the slots
    `idx` (the caller holds _predecomp_lock, so slots and rows are of
    one table): the device mirror and the slots, or with a mesh the
    rows themselves, taken here because the batch axis is about to be
    split and the table is not. The slots are stamped as used."""
    _predecomp.touch(idx)
    if mesh is None:
        return _predecomp.mirror(), idx
    return (_predecomp.rows[idx],)


def _predecomp_rows(pk_np, mesh):
    """The batch's keys' rows as _table_rows hands them over (mirror and
    slots, or rows), or None when it takes the fused full kernel: a first
    sighting of keys that fill one lane each (a one-shot batch must not
    pay for decompressing keys it may never show again; they are marked
    seen). Any later batch made of seen keys, and any batch that shows
    its missing keys in several lanes (a window's chunk, a joiner's
    lanes), decompresses them ONCE, on the host (_decompress_keys), and
    fills the table. A batch
    whose whole key sequence was resolved before gets the same slots
    again, read-only and shared between dispatches."""
    raw = pk_np.tobytes()
    with _predecomp_lock:
        memo = _predecomp_memo.get(raw)
        if memo is not None and memo[0] == _predecomp.epoch:
            _predecomp_memo.move_to_end(raw)
            _predecomp_note("hit", how="reused")
            return _table_rows(memo[1], mesh)
        idx, miss, second = _predecomp.lookup(pk_np)
        _predecomp_note_lanes(idx.size - second, second)
        if not miss.any():
            idx.flags.writeable = False
            _predecomp_memo[raw] = (_predecomp.epoch, idx)
            while len(_predecomp_memo) > _PREDECOMP_MEMO_MAX:
                _predecomp_memo.popitem(last=False)
            _predecomp_note("hit", how="built")
            return _table_rows(idx, mesh)
        new = np.unique(pk_np[miss], axis=0)
        fresh = [k for k in map(bytes, new) if k not in _predecomp_seen]
        for k in fresh:
            _predecomp_seen[k] = True
        while len(_predecomp_seen) > 4 * _PREDECOMP_MAX_KEYS:
            _predecomp_seen.popitem(last=False)
        distinct = new.shape[0] + np.unique(idx[~miss]).size
        # a batch that shows a missing key in several lanes is repeat
        # traffic within itself: the full kernel would decompress that
        # key once a lane, the host does it once
        repeated = int(miss.sum()) > new.shape[0]
        if (fresh and not repeated) or distinct > _predecomp.keys.shape[0]:
            # unseen keys, each in one lane: fused full kernel (nothing
            # decompressed that may never show again); the NEXT batch
            # over these keys fills rows. So does a batch of more keys
            # than the table has slots
            _predecomp_note("full")
            return None
        _predecomp_note("fill", how="built")
    # repeat traffic over keys that are not resident: decompress them
    # once (outside the lock) and store the rows of those still missing
    # (a concurrent fill of the same keys is harmless: what it stored
    # is found, not stored twice)
    rows = _decompress_keys(new)
    with _predecomp_lock:
        idx, miss, _ = _predecomp.lookup(pk_np)
        # the batch's resident keys first, so that none of them is put
        # out for one of its others
        _predecomp.touch(idx[~miss])
        if miss.any():
            # sorted as `new` is: the same keys, unless another fill or
            # an eviction moved the table in between
            missing = np.unique(pk_np[miss], axis=0)
            if not np.array_equal(missing, new):
                rows = _decompress_keys(missing)
            evicted = _predecomp.insert(missing, *rows)
            if evicted:
                _predecomp_note("evict", evicted)
            idx, _, _ = _predecomp.lookup(pk_np)
        return _table_rows(idx, mesh)


# ---------------------------------------------------------------------------
# Batched signing (TPU fixed-base ladder + native host finalization)
# ---------------------------------------------------------------------------
# RFC 8032 signing, batched: r = SHA512(prefix||M) mod L (native C),
# R = r*B on device (ladder_pallas._sign_kernel — the fixed-base subset
# of the verify ladder), k/s finalization native. Byte-identical to
# OpenSSL's Ed25519 signatures for the same seed+message, so the bench
# chains it signs verify under ANY conforming implementation. It is
# what makes building 64M-signature lite chains (BASELINE config 5 at
# full scale) feasible; its rate is not measured on the attached chip.

_sign_params_cache: dict = {}


def _public_key(seed: bytes) -> bytes:
    """The RFC 8032 public key of a seed: OpenSSL's (~40 us) where
    `cryptography` is installed, else the reference's pure-Python point
    multiply (~2.4 ms: 10,000 signers cost 24 s of it)."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import \
            Ed25519PrivateKey
    except ImportError:  # pragma: no cover
        from tendermint_tpu.utils import ed25519_ref as ref
        return ref.public_key(seed)
    return Ed25519PrivateKey.from_private_bytes(
        seed).public_key().public_bytes_raw()


def signing_params(seed: bytes):
    """(a32, prefix32, pk32) for an RFC 8032 seed, cached per seed."""
    ent = _sign_params_cache.get(seed)
    if ent is None:
        h = hashlib.sha512(seed).digest()
        a = bytearray(h[:32])
        a[0] &= 248
        a[31] &= 127
        a[31] |= 64
        ent = (bytes(a), h[32:], _public_key(seed))
        # holds one validator set of the size the predecomp cache holds;
        # one seed more puts the oldest out, never the whole set
        if len(_sign_params_cache) >= _PREDECOMP_MAX_KEYS:
            del _sign_params_cache[next(iter(_sign_params_cache))]
        _sign_params_cache[seed] = ent
    return ent


@jax.jit
def _sign_rb_pallas(r_u8):
    from tendermint_tpu.ops import ladder_pallas
    return ladder_pallas.sign_pallas_rB(r_u8)


def sign_batch_async(seeds, msgs):
    """Dispatch batched signing WITHOUT blocking: returns a zero-arg
    resolver yielding the signature list. On a TPU the nonce hashes
    run now (native, GIL released), the device R = r*B chunks are
    enqueued, and the resolver fetches them and finalizes s = r + k*a
    natively — a chain builder constructs its header/vote objects
    while the device works. There a missing native prep extension is
    an error. Any other backend signs scalar on the host (OpenSSL),
    counted as sign_scalar."""
    n = len(msgs)
    if n == 0:
        return lambda: []
    if not _pallas_available():
        _note_kernel("sign_scalar")
        from tendermint_tpu.utils import ed25519_ref as ref
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import \
                Ed25519PrivateKey
            signers = {}
            out = []
            for seed, m in zip(seeds, msgs):
                s = signers.get(seed)
                if s is None:
                    s = Ed25519PrivateKey.from_private_bytes(seed).sign
                    signers[seed] = s
                out.append(s(m))
        except ImportError:  # pragma: no cover
            out = [ref.sign(seed, m) for seed, m in zip(seeds, msgs)]
        return lambda: out
    from tendermint_tpu import native
    mod = native._prep()
    if mod is None:
        raise RuntimeError(
            "batched signing on a TPU needs the native prep extension "
            "(tendermint_tpu/native/prep.cpp), which is switched off")
    params = [signing_params(seed) for seed in seeds]
    a_cat = b"".join(p[0] for p in params)
    pre_cat = b"".join(p[1] for p in params)
    pk_cat = b"".join(p[2] for p in params)
    r_cat = mod.sign_phase1(pre_cat, msgs)
    r_np = np.frombuffer(r_cat, np.uint8).reshape(n, 32)
    # device: enc(r*B) in BATCH_CHUNK-sized dispatches (512-tile padded)
    # 16384-sig chunks (32 grid tiles): signing is bulk-only (chain
    # builders, load generators), so fewer/larger dispatches beat the
    # verifier's latency-sensitive 8192
    chunk = 16384
    pending = []
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        m = 512 * ((hi - lo + 511) // 512)
        t0 = time.perf_counter()
        r_enc = _sign_rb_pallas(jnp.asarray(_pad_to(r_np[lo:hi], m)))
        _note_kernel("sign_pallas", f"sign_pallas[{m}]",
                     time.perf_counter() - t0)
        pending.append((hi - lo, r_enc))

    def resolve() -> list:
        if len(pending) > 1:
            # chunks are fetched from the verifier's pool, several at a
            # time (whether that beats a serial fetch: not measured on
            # the attached chip)
            from tendermint_tpu.models.verifier import _fetch_pool_get
            arrs = list(_fetch_pool_get().map(
                lambda p: np.asarray(p[1]), pending))
        else:
            arrs = [np.asarray(pending[0][1])]
        renc_cat = np.concatenate(
            [a[:real] for (real, _), a in zip(pending, arrs)],
            axis=0).tobytes()
        sig_cat = mod.sign_phase2(renc_cat, pk_cat, msgs, r_cat, a_cat)
        return [sig_cat[64 * i:64 * (i + 1)] for i in range(n)]

    return resolve


def sign_batch(seeds, msgs) -> list:
    """Batched Ed25519 signing: aligned seeds[i] signs msgs[i].
    Returns 64-byte signatures, byte-identical to scalar RFC 8032 /
    OpenSSL output. See sign_batch_async for which backend signs."""
    return sign_batch_async(seeds, msgs)()


# ---------------------------------------------------------------------------
# End-to-end batch verify (host prep + device kernel)
# ---------------------------------------------------------------------------

def _pad_to(x: np.ndarray, n: int) -> np.ndarray:
    if x.shape[0] == n:
        return x
    pad = np.zeros((n - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0)


def _bucket(n: int, min_size: int = 8) -> int:
    """Round batch size up to a power of two. This bounds the set of
    compiled kernel shapes to ~14 total — each distinct pallas shape
    costs a full trace + Mosaic compile (tens of seconds cold), which
    dwarfs the <2x padding compute it avoids. Callers that want zero
    padding chunk at BATCH_CHUNK first."""
    b = min_size
    while b < n:
        b *= 2
    return b


def verify_batch_async(pubkeys, msgs, sigs, mesh=None):
    """Dispatch one padded batch WITHOUT blocking: returns
    (device_result, precheck bool[N]). jax dispatch is asynchronous, so
    a caller with several chunks can enqueue them all and let device
    compute overlap host prep + transfers."""
    with trace.span("verify.prep", n=len(pubkeys)):
        pk, rb, s_bytes, h_bytes, pre = prepare_batch_bytes(
            pubkeys, msgs, sigs)
    res = verify_prepared_async(pk, rb, s_bytes, h_bytes, mesh=mesh)
    return res, pre


def verify_prepared_async(pk, rb, s_bytes, h_bytes, mesh=None):
    """Dispatch already-prepared arrays (native.prep_items output or
    prepare_batch_bytes minus the precheck): pads, routes through the
    predecompressed-pubkey cache, picks the kernel (_dispatch). `mesh`
    (parallel/mesh.make_mesh) shards the batch axis over its devices.
    Returns the device result; the caller masks with its precheck."""
    n = pk.shape[0]
    ndev = 1 if mesh is None else mesh.devices.size
    # buckets and mesh sizes are both powers of two, so a bucket of at
    # least the mesh size splits evenly over it
    m = _bucket(n, min_size=max(8, ndev))
    if 64 < m < 512 * ndev and _pallas_available():
        # pad mid-size batches (100-500 sigs: real commits) up to one
        # fused-kernel tile per device: more lanes, but they stay in
        # VMEM where the jnp ladder round-trips every op through HBM
        m = 512 * ndev
    pk_p = _pad_to(pk, m)
    rb_p, sb_p, hb_p = (_pad_to(rb, m), _pad_to(s_bytes, m),
                        _pad_to(h_bytes, m))
    if m >= _PREDECOMP_MIN_BATCH:
        # stable-valset fast path: batches over pubkeys seen before
        # skip point decompression (cache keyed per pubkey)
        res = _verify_cached_predecomp(pk_p, rb_p, sb_p, hb_p, mesh)
        if res is not None:
            return res
    return _dispatch("full", mesh, pk_p, rb_p, sb_p, hb_p)


def verify_batch(pubkeys, msgs, sigs, mesh=None) -> np.ndarray:
    """Verify N (pubkey, msg, sig) triples; returns bool[N].

    Batches are padded to power-of-two sizes so repeated calls hit the jit
    cache.
    """
    n = len(pubkeys)
    if n == 0:
        return np.zeros(0, np.bool_)
    res, pre = verify_batch_async(pubkeys, msgs, sigs, mesh=mesh)
    return np.asarray(res)[:n] & pre
