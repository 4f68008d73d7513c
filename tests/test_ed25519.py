"""Differential tests: TPU batch-verify kernel vs pure-Python RFC 8032 ref."""

import contextlib
import hashlib
import random
import sys
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.ops import curve, ed25519, field as fe
from tendermint_tpu.utils import ed25519_ref as ref

rng = random.Random(99)


def seeds(n):
    return [rng.randbytes(32) for _ in range(n)]


def test_curve_ops_match_reference():
    # batched add/double/encode vs python ints
    pts_int = [ref.point_mul(rng.randrange(1, ref.L), ref.BASE) for _ in range(4)]
    pts_aff = []
    for X, Y, Z, _ in pts_int:
        zi = pow(Z, ref.P - 2, ref.P)
        pts_aff.append((X * zi % ref.P, Y * zi % ref.P))
    batch = tuple(
        jnp.stack([comp for comp in comps])
        for comps in zip(*[curve.from_ints(x, y) for x, y in pts_aff])
    )
    # double
    d = curve.double(batch)
    enc = np.asarray(curve.encode(d))
    for i, p in enumerate(pts_int):
        expect = ref.point_compress(ref.point_add(p, p))
        assert enc[i].tobytes() == expect
    # add p[i] + p[(i+1)%4]
    rolled = tuple(jnp.roll(c, -1, axis=0) for c in batch)
    s = curve.add(batch, rolled)
    enc2 = np.asarray(curve.encode(s))
    for i, p in enumerate(pts_int):
        expect = ref.point_compress(ref.point_add(p, pts_int[(i + 1) % 4]))
        assert enc2[i].tobytes() == expect
    # adding identity is a no-op (completeness)
    ident = curve.identity((4,))
    s2 = curve.add(batch, ident)
    enc3 = np.asarray(curve.encode(s2))
    for i, (x, y) in enumerate(pts_aff):
        expect = ref.point_compress((x, y, 1, x * y % ref.P))
        assert enc3[i].tobytes() == expect


def test_decompress_valid_and_invalid():
    sds = seeds(3)
    pks = [ref.public_key(s) for s in sds]
    bad = bytearray(pks[0])
    bad[0] ^= 1  # almost surely not on curve
    candidates = pks + [bytes(bad)]
    arr = jnp.asarray(np.stack([np.frombuffer(c, np.uint8) for c in candidates]))
    pt, ok = curve.decompress(arr)
    ok = np.asarray(ok)
    expected = [ref.point_decompress(c) is not None for c in candidates]
    assert list(ok) == expected
    enc = np.asarray(curve.encode(pt))
    for i, c in enumerate(candidates):
        if expected[i]:
            assert enc[i].tobytes() == c


def _edge_encodings():
    """y at and around 0, 1, p and 2**255, each with and without the
    sign bit: y >= p, x = 0 under the sign bit, the largest encoding."""
    p = (1 << 255) - 19
    return [(y | (sign << 255)).to_bytes(32, "little")
            for y in (0, 1, 2, p - 2, p - 1, p, p + 1, p + 2, p + 18,
                      (1 << 255) - 1)
            for sign in (0, 1)]


@pytest.mark.parametrize("keys", [
    "public_keys", "random_strings", "edge_encodings"])
def test_the_hosts_decompression_is_the_devices_row_for_row(keys):
    """A fill stores what _decompress_keys computes in Python integers;
    the *_pre kernels were written against _decompress_to_bytes, the
    device's field arithmetic. Equal to the byte, valid or not."""
    made = {"public_keys": [ref.public_key(s) for s in seeds(24)],
            "random_strings": [rng.randbytes(32) for _ in range(200)],
            "edge_encodings": _edge_encodings()}[keys]
    pk = np.stack([np.frombuffer(k, np.uint8) for k in made])
    want = [np.asarray(a) for a in ed25519._decompress_to_bytes(pk)]
    got = ed25519._decompress_keys(pk)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    valid = want[2].tolist()
    assert valid == [ref.point_decompress(k) is not None for k in made]
    if keys == "public_keys":
        assert all(valid)
    else:
        assert 0 < sum(valid) < len(valid)
    assert [a.shape for a in ed25519._decompress_keys(pk[:0])] == \
        [(0, 32), (0, 32), (0,)]


def test_a_batch_that_repeats_its_unseen_keys_fills_at_once():
    """The full kernel decompresses a key once a lane, the host once a
    key: a first sighting goes to the full kernel only where every
    missing key fills one lane (a commit of distinct keys); a window's
    chunk or a joiner's lanes, one key in many lanes, fill the table at
    their first sighting and take the predecompressed kernel, so a
    chain's sync dispatches no full kernel at all."""
    keys = [ref.public_key(s) for s in seeds(8)]
    once = np.stack([np.frombuffer(k, np.uint8) for k in keys])
    with predecomp_sandbox(min_batch=8):
        s0 = ed25519.predecomp_stats()
        assert ed25519._predecomp_rows(once, None) is None
        s1 = ed25519.predecomp_stats()
        assert (s1["full"], s1["fill"]) == (s0["full"] + 1, s0["fill"])
        assert ed25519._predecomp_rows(once, None) is not None
    with predecomp_sandbox(min_batch=8):
        joiner = np.repeat(once[:1], 8, axis=0)         # one key, 8 lanes
        s0 = ed25519.predecomp_stats()
        handed = ed25519._predecomp_rows(joiner, None)
        s1 = ed25519.predecomp_stats()
        assert handed is not None
        assert (s1["full"], s1["fill"]) == (s0["full"], s0["fill"] + 1)
        chunk = np.concatenate([once, once])            # each key twice
        assert ed25519._predecomp_rows(chunk, None) is not None
        assert ed25519.predecomp_stats()["full"] == s0["full"]
        assert ed25519.predecomp_stats()["keys"] == 8


def test_verify_batch_good_and_bad():
    from bench_util import fast_signer, scalar_verify_one
    sds = seeds(6)
    pks = [ref.public_key(s) for s in sds]
    msgs = [rng.randbytes(rng.randrange(0, 100)) for _ in sds]
    sigs = [fast_signer(s)(m) for s, m in zip(sds, msgs)]

    # sanity: the independent scalar backend verifies its own sigs
    _sv = scalar_verify_one()
    assert all(_sv(p, m, s) for p, m, s in zip(pks, msgs, sigs))

    # corruptions
    bad_sig = bytearray(sigs[1]); bad_sig[0] ^= 1
    bad_msg = msgs[2] + b"x"
    wrong_key = pks[3]
    high_s = bytearray(sigs[4])
    s_int = int.from_bytes(bytes(high_s[32:]), "little") + ref.L
    high_s[32:] = s_int.to_bytes(32, "little")

    pubkeys = [pks[0], pks[1], pks[2], wrong_key, pks[4], pks[5]]
    messages = [msgs[0], msgs[1], bad_msg, msgs[4], msgs[4], msgs[5]]
    signatures = [sigs[0], bytes(bad_sig), sigs[2], sigs[4], bytes(high_s), sigs[5]]
    expected = [True, False, False, False, False, True]

    got = ed25519.verify_batch(pubkeys, messages, signatures)
    assert list(got) == expected
    # agreement with the python reference on every case
    pyref = [ref.verify(p, m, s) for p, m, s in zip(pubkeys, messages, signatures)]
    assert list(got) == pyref


def test_verify_batch_padding_and_empty():
    assert ed25519.verify_batch([], [], []).shape == (0,)
    sds = seeds(3)
    pks = [ref.public_key(s) for s in sds]
    msgs = [b"a", b"bb", b"ccc"]
    sigs = [ref.sign(s, m) for s, m in zip(sds, msgs)]
    got = ed25519.verify_batch(pks, msgs, sigs)
    assert got.all() and got.shape == (3,)


def test_predecompressed_cache_path_matches_full():
    """The stable-valset fast path (the table of predecompressed rows,
    ops/ed25519._verify_cached_predecomp): the first occurrence of a
    pubkey batch takes the full kernel, repeats take the *_pre kernel
    with the table's (-A) bytes gathered by index — verdicts must be
    identical across calls, including invalid pubkeys and tampered
    signatures."""
    from bench_util import fast_signer

    rng = random.Random(99)
    n = 8
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = rng.randbytes(32)
        m = b"pre-cache %d" % i
        pubs.append(ref.public_key(seed))
        msgs.append(m)
        sigs.append(fast_signer(seed)(m))
    # sprinkle failures: tampered sig, wrong msg, non-point pubkey
    sigs[5] = sigs[5][:32] + bytes([sigs[5][32] ^ 1]) + sigs[5][33:]
    msgs[1] = b"wrong"
    pubs[7] = b"\xff" * 32

    expect = [i not in (5, 1, 7) for i in range(n)]
    # run the cache at batch 8 (shapes earlier tests already compiled —
    # the production 64 gate exists to spare one-shot SMALL batches the
    # decompress dispatch, not because the cache logic differs by size)
    with predecomp_sandbox():
        r1 = ed25519.verify_batch(pubs, msgs, sigs)  # full kernel, records
        assert r1.tolist() == expect
        r2 = ed25519.verify_batch(pubs, msgs, sigs)  # fills + uses table
        assert r2.tolist() == expect
        # one slot per distinct key (incl. the invalid one, kept with
        # ok=False so forged keys never re-pay the sqrt)
        assert len(ed25519._predecomp) == n, "cache did not engage"
        assert all(resident(*pubs))
        r3 = ed25519.verify_batch(pubs, msgs, sigs)  # cache hit
        assert r3.tolist() == expect
        assert ed25519._predecomp_stats["hit"] >= 1
        # the point of per-KEY rows: a REORDERED batch over the same
        # keys is still a pure cache hit (batch-content keying missed)
        hits0 = ed25519._predecomp_stats["hit"]
        perm = list(range(n))[::-1]
        r4 = ed25519.verify_batch([pubs[i] for i in perm],
                                  [msgs[i] for i in perm],
                                  [sigs[i] for i in perm])
        assert r4.tolist() == [expect[i] for i in perm]
        assert ed25519._predecomp_stats["hit"] == hits0 + 1


def test_predecomp_telemetry_stays_meaningful_under_churn():
    """Valset rotation vs the table's capacity (ISSUE 11 satellite): a
    rotating valset must show up as full->fill->hit cycles per
    rotation, evictions must be COUNTED (they were invisible before — a
    churning valset quietly degraded every hit into a re-fill), and the
    tm_verifier_predecomp_* counters must mirror the host stats."""
    from tendermint_tpu import telemetry

    from bench_util import fast_signer

    def batch(tag, n=8):
        pubs, msgs, sigs = [], [], []
        for i in range(n):
            seed = bytes([tag, i]) * 16
            m = b"churn %d.%d" % (tag, i)
            pubs.append(ref.public_key(seed))
            msgs.append(m)
            sigs.append(fast_signer(seed)(m))
        return pubs, msgs, sigs

    with predecomp_sandbox(max_keys=8):     # one valset's worth of slots
        s0 = ed25519.predecomp_stats()
        ev0 = telemetry.value("verifier_predecomp_evictions_total") or 0.0
        a = batch(1)
        for _ in range(3):  # full (first sighting) -> fill -> hit
            assert ed25519.verify_batch(*a).all()
        s1 = ed25519.predecomp_stats()
        assert s1["full"] == s0["full"] + 1
        assert s1["fill"] == s0["fill"] + 1
        assert s1["hit"] == s0["hit"] + 1
        assert s1["evict"] == s0["evict"]
        assert s1["keys"] == 8

        # rotation: a new valset's repeat traffic evicts the old rows
        # (capacity 8) and runs its own full->fill->hit cycle — the
        # hit/fill split stays meaningful instead of silently decaying
        b = batch(2)
        for _ in range(3):
            assert ed25519.verify_batch(*b).all()
        s2 = ed25519.predecomp_stats()
        assert s2["full"] == s1["full"] + 1
        assert s2["fill"] == s1["fill"] + 1
        assert s2["hit"] == s1["hit"] + 1
        assert s2["evict"] == s1["evict"] + 8  # old valset's rows
        assert s2["keys"] == 8
        assert 0.0 < s2["hit_rate"] < 1.0

        # telemetry mirrors the host stats (the new eviction counter
        # most of all — that is the one that was invisible)
        assert (telemetry.value("verifier_predecomp_evictions_total")
                - ev0) == 8.0
        assert telemetry.value("verifier_predecomp_keys") == 8.0
        assert telemetry.value("verifier_predecomp_batches_total",
                               {"outcome": "hit"}) >= 2.0


def test_scalar_openssl_matches_pure_oracle():
    """PubKey.verify/verify_any route through OpenSSL (~170x faster);
    verdicts must agree with the pure RFC 8032 oracle on valid,
    tampered, truncated, garbage AND adversarial non-canonical
    encodings (OpenSSL's leniency gap routes back to the oracle — a
    verdict split there would be a consensus fork)."""
    import random

    import pytest as _pytest

    from tendermint_tpu.types import keys as keys_mod
    from tendermint_tpu.types.keys import PubKey, _openssl_verify
    from tendermint_tpu.utils import ed25519_ref as ref

    _pytest.importorskip("cryptography")

    p255 = (1 << 255) - 19
    rng = random.Random(4242)
    for i in range(30):
        seed = rng.randbytes(32)
        pk = ref.public_key(seed)
        msg = rng.randbytes(rng.randrange(0, 64))
        sig = ref.sign(seed, msg)
        cases = [
            (pk, msg, sig),                                   # valid
            (pk, msg + b"x", sig),                            # wrong msg
            (pk, msg, sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]),
            (pk, msg, sig[:-1]),                              # short sig
            (pk, msg, rng.randbytes(64)),                     # garbage
            (rng.randbytes(32), msg, sig),                    # wrong key
        ]
        for p, m, s in cases:
            want = ref.verify(p, m, s)
            assert PubKey(p).verify(m, s) == want, (i, p.hex())

    # adversarial non-canonical encodings: x=0 identity rows with the
    # sign bit set, and y >= p — _openssl_verify must DECLINE (None)
    # and the routed verdict must equal the oracle's
    msg = b"adversarial"
    ncid = (1).to_bytes(32, "little")
    ncid = ncid[:31] + bytes([ncid[31] | 0x80])        # y=1, sign=1
    ncid2 = (p255 - 1).to_bytes(32, "little")
    ncid2 = ncid2[:31] + bytes([ncid2[31] | 0x80])     # y=-1, sign=1
    ybig = (p255 + 2).to_bytes(32, "little")           # y >= p
    for bad in (ncid, ncid2, ybig):
        for pkey, sg in ((bad, bad + bytes(32)),
                         (ref.public_key(b"\x01" * 32), bad + bytes(32)),
                         (bad, ref.sign(b"\x01" * 32, msg))):
            assert _openssl_verify(pkey, msg, sg) is None, bad.hex()
            assert PubKey(pkey).verify(msg, sg) == \
                ref.verify(pkey, msg, sg)

    # the pure-fallback branch (no cryptography) still verifies
    orig = keys_mod._ossl_pub_cls
    try:
        keys_mod._ossl_pub_cls = False
        seed = b"\x05" * 32
        pk = ref.public_key(seed)
        sig = ref.sign(seed, msg)
        assert PubKey(pk).verify(msg, sig)
        assert not PubKey(pk).verify(msg + b"!", sig)
    finally:
        keys_mod._ossl_pub_cls = orig




# --------------------------------------------------------------------------
# The table of predecompressed rows (ISSUE 41): a batch gets its rows by
# INDEX, resolved by array operations, and the memo in front of the table
# (ISSUE 25) keeps a key sequence's resolved slots; the table stays the
# source of truth.
# --------------------------------------------------------------------------

ASSEMBLED = "verifier_predecomp_assembled_total"
LANES = "verifier_predecomp_lanes_total"


@contextlib.contextmanager
def predecomp_sandbox(min_batch=8, max_keys=None):
    """An empty table (of `max_keys` slots), memo and sighted set,
    counters on, the gate at the small shapes the earlier tests
    compiled; everything as it was afterwards."""
    from tendermint_tpu import telemetry
    was_enabled = telemetry.enabled()
    orig = ed25519._PREDECOMP_MIN_BATCH, ed25519._PREDECOMP_MAX_KEYS
    caches = (ed25519._predecomp, ed25519._predecomp_seen,
              ed25519._predecomp_memo)
    telemetry.set_enabled(True)
    ed25519._PREDECOMP_MIN_BATCH = min_batch
    if max_keys is not None:
        ed25519._PREDECOMP_MAX_KEYS = max_keys
    for c in caches:
        c.clear()
    try:
        yield
    finally:
        telemetry.set_enabled(was_enabled)
        ed25519._PREDECOMP_MIN_BATCH, ed25519._PREDECOMP_MAX_KEYS = orig
        for c in caches:
            c.clear()


def assembled():
    """(built, reused) of the counter, and the cache's own stats."""
    from tendermint_tpu import telemetry
    return (tuple(telemetry.value(ASSEMBLED, {"how": how}) or 0.0
                  for how in ("built", "reused")),
            ed25519.predecomp_stats())


def lanes():
    """(index, second) of the lookup's lane counter."""
    from tendermint_tpu import telemetry
    return tuple(telemetry.value(LANES, {"how": how}) or 0.0
                 for how in ("index", "second"))


def row_of(k):
    """A key's row as fill_by_hand leaves it, without the sqrt: the row
    bytes are a function of the key, which is all the cache layer knows
    of them."""
    d = hashlib.sha512(k).digest()
    return d[:32], d[32:], bool(d[0] & 1)


def as_rows(keys):
    return np.frombuffer(b"".join(keys), np.uint8).reshape(len(keys), 32)


def resident(*keys):
    """Which of `keys` hold a slot of the table."""
    return (~ed25519._predecomp.lookup(as_rows(keys))[1]).tolist()


def fill_by_hand(keys):
    """Rows for distinct `keys`, none resident, as a fill stores them:
    one tick for the batch, then the insert."""
    keys = sorted(keys)
    rows = [row_of(k) for k in keys]
    with ed25519._predecomp_lock:
        ed25519._predecomp.touch(np.zeros(0, np.int32))
        return ed25519._predecomp.insert(
            as_rows(keys), as_rows([r[0] for r in rows]),
            as_rows([r[1] for r in rows]), np.array([r[2] for r in rows]))


def handed_rows(handed):
    """What a `pre` program computes on, as ops/ed25519._rows_at takes
    it apart: the mirror gathered at the slots, or (a mesh) the rows
    taken on the host."""
    rows = np.asarray(handed[0])
    assert rows.dtype == np.uint8 and rows.shape[1] == 65
    if len(handed) == 2:
        idx = handed[1]
        assert idx.dtype == np.int32 and idx.min() >= 0
        assert idx.max() < len(ed25519._predecomp)
        rows = rows[idx]
    assert set(np.unique(rows[:, 64])) <= {0, 1}
    return rows[:, :32], rows[:, 32:64], rows[:, 64] != 0


def rows_match_their_keys(handed, keys):
    xn, y, ok = handed_rows(handed)
    assert xn.shape == y.shape == (len(keys), 32) and ok.shape == (len(keys),)
    for i, k in enumerate(keys):
        want = row_of(k)
        assert xn[i].tobytes() == want[0], i
        assert y[i].tobytes() == want[1], i
        assert bool(ok[i]) == want[2], i


def valset(n, tag=0):
    return [hashlib.sha256(b"memo val %d.%d" % (tag, i)).digest()
            for i in range(n)]


LAYOUTS = {
    # 64 validators all signing 128 commits, in validator-set order
    "64_keys_x128_set_order": lambda: valset(64) * 128,
    "8192_distinct": lambda: valset(8192),
    # the same multiset as the first, each validator's rows together
    "64_keys_x128_grouped": lambda: [k for k in valset(64)
                                     for _ in range(128)],
    # 100 commits and a tail of padding rows (the all-zero key)
    "tail_of_zero_key_rows": lambda: valset(64) * 100 + [bytes(32)] * 1792,
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_reused_slots_equal_slots_resolved_with_the_memo_emptied(layout):
    keys = LAYOUTS[layout]()
    pk = as_rows(keys)
    with predecomp_sandbox():
        fill_by_hand(set(keys))
        (b0, r0), s0 = assembled()
        i0, x0 = lanes()
        first = ed25519._predecomp_rows(pk, None)
        again = ed25519._predecomp_rows(pk.copy(), None)
        (b1, r1), s1 = assembled()
        assert (b1 - b0, r1 - r0) == (1.0, 1.0)
        assert s1["hit"] == s0["hit"] + 2 and s1["fill"] == s0["fill"]
        # the same slots and, no fill between them, the same mirror
        assert all(a is b for a, b in zip(first, again))
        # the lookup ran once, settled every lane and walked for none
        assert lanes() == (i0 + len(keys), x0)
        ed25519._predecomp_memo.clear()
        built = ed25519._predecomp_rows(pk, None)
        assert built[1] is not again[1]
        assert built[1].dtype == again[1].dtype == np.int32
        assert built[1].tobytes() == again[1].tobytes()
        rows_match_their_keys(again, keys)
        # the same keys in another order are another sequence: never
        # answered with this one's slots, before or after it is memoised
        other = keys[1:] + keys[:1]
        if layout != "8192_distinct":
            other = sorted(keys)
        assert sorted(other) == sorted(keys) and other != keys
        for _ in range(2):
            rows_match_their_keys(
                ed25519._predecomp_rows(as_rows(other), None), other)
        rows_match_their_keys(ed25519._predecomp_rows(pk, None), keys)
        (b2, r2), _ = assembled()
        assert (b2 - b1, r2 - r1) == (2.0, 2.0)


def signed_batch(tag, n=8):
    from bench_util import fast_signer
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([tag, i]) * 16
        m = b"memo %d.%d" % (tag, i)
        pubs.append(ref.public_key(seed))
        msgs.append(m)
        sigs.append(fast_signer(seed)(m))
    return pubs, msgs, sigs


def test_reuse_hands_out_rows_not_verdicts():
    """Two batches over identical keys, the second with one tampered
    signature: the slots are reused, each lane's verdict is its own."""
    pubs, msgs, sigs = signed_batch(11)
    pubs[6] = b"\xff" * 32          # a key that is no point, cached as such
    bad = list(sigs)
    bad[3] = bad[3][:7] + bytes([bad[3][7] ^ 4]) + bad[3][8:]   # R, not s
    with predecomp_sandbox():
        for _ in range(3):          # full, fill, hit: resolved and kept
            ed25519.verify_batch(pubs, msgs, sigs)
        (b0, r0), _ = assembled()
        good_got = ed25519.verify_batch(pubs, msgs, sigs)
        bad_got = ed25519.verify_batch(pubs, msgs, bad)
        (b1, r1), _ = assembled()
        assert (b1 - b0, r1 - r0) == (0.0, 2.0)
    assert good_got.tolist() == [ref.verify(p, m, s)
                                 for p, m, s in zip(pubs, msgs, sigs)]
    assert bad_got.tolist() == [ref.verify(p, m, s)
                                for p, m, s in zip(pubs, msgs, bad)]
    assert good_got.tolist() == [i != 6 for i in range(8)]
    assert bad_got.tolist() == [i not in (3, 6) for i in range(8)]


def test_a_cleared_table_is_not_answered_from_the_memo():
    a = signed_batch(12)
    with predecomp_sandbox():
        for _ in range(4):          # full, fill, hit, reuse
            assert ed25519.verify_batch(*a).all()
        (b0, r0), s0 = assembled()
        assert r0 >= 1.0 and len(ed25519._predecomp_memo) == 1
        ed25519._predecomp.clear()
        ed25519._predecomp_seen.clear()
        assert len(ed25519._predecomp) == 0
        for _ in range(3):          # full -> fill -> hit again
            assert ed25519.verify_batch(*a).all()
        (b1, r1), s1 = assembled()
        assert [s1[k] - s0[k] for k in ("full", "fill", "hit")] == [1, 1, 1]
        assert (b1 - b0, r1 - r0) == (2.0, 0.0)     # fill and hit built
        assert ed25519.verify_batch(*a).all()
        (b2, r2), s2 = assembled()
        assert (b2 - b1, r2 - r1) == (0.0, 1.0) and s2["hit"] == s1["hit"] + 1


def test_an_evicted_key_sends_its_sequence_through_fill_again():
    a = signed_batch(13)
    b = signed_batch(14)
    mixed = [x[:7] + y[:1] for x, y in zip(a, b)]   # seven of a's, one new
    with predecomp_sandbox(max_keys=8):
        for _ in range(4):
            assert ed25519.verify_batch(*a).all()
        (b0, r0), s0 = assembled()
        for _ in range(2):          # full, then a fill that needs a 9th slot
            assert ed25519.verify_batch(*mixed).all()
        _, s1 = assembled()
        assert s1["evict"] == s0["evict"] + 1 and s1["keys"] == 8
        # the one key the filling batch did not use went, none of its own
        assert resident(*a[0]) == [True] * 7 + [False]
        assert all(resident(*mixed[0]))
        (b1, r1), s1 = assembled()
        assert len(ed25519._predecomp_memo) == 1    # a's, of an epoch ago
        assert ed25519.verify_batch(*a).all()
        (b2, r2), s2 = assembled()
        assert s2["fill"] == s1["fill"] + 1 and s2["hit"] == s1["hit"]
        assert (b2 - b1, r2 - r1) == (1.0, 0.0)
        # the telemetry mirrors the stats, as it did before the memo
        from tendermint_tpu import telemetry
        assert telemetry.value("verifier_predecomp_keys") == 8.0


def test_a_reuse_counts_as_the_hit_it_is_and_refreshes_recency():
    old, young, newer = (valset(8, tag=t) for t in (1, 2, 3))
    keys = old * 2
    with predecomp_sandbox(max_keys=16):
        fill_by_hand(old)
        ed25519._predecomp_rows(as_rows(keys), None)
        fill_by_hand(young)                 # younger than every key of it
        from tendermint_tpu import telemetry
        hit0 = telemetry.value("verifier_predecomp_batches_total",
                               {"outcome": "hit"}) or 0.0
        (b0, r0), s0 = assembled()
        ed25519._predecomp_rows(as_rows(keys), None)
        (b1, r1), s1 = assembled()
        assert (b1 - b0, r1 - r0) == (0.0, 1.0)
        assert s1["hit"] == s0["hit"] + 1
        assert telemetry.value("verifier_predecomp_batches_total",
                               {"outcome": "hit"}) == hit0 + 1.0
        # one stamp a use: the reused sequence's slots are the newest
        table = ed25519._predecomp
        slots = {k: int(table.lookup(as_rows([k]))[0][0])
                 for k in old + young}
        assert (min(table.stamp[slots[k]] for k in old)
                > max(table.stamp[slots[k]] for k in young))
        # so a fill past the table's room puts the others out
        assert fill_by_hand(newer) == 8
        assert all(resident(*old, *newer)) and not any(resident(*young))


def test_the_memo_holds_at_most_its_bound():
    keys = valset(16, tag=3)
    with predecomp_sandbox():
        fill_by_hand(keys)
        seqs = [keys[i:] + keys[:i] for i in range(12)]
        for seq in seqs:
            ed25519._predecomp_rows(as_rows(seq), None)
            assert len(ed25519._predecomp_memo) <= ed25519._PREDECOMP_MEMO_MAX
        assert ed25519._PREDECOMP_MEMO_MAX == 8
        # the least recently used went: the last eight are reuses
        (b0, r0), _ = assembled()
        for seq in seqs[4:]:
            rows_match_their_keys(
                ed25519._predecomp_rows(as_rows(seq), None), seq)
        (b1, r1), _ = assembled()
        assert (b1 - b0, r1 - r0) == (0.0, 8.0)
        ed25519._predecomp_rows(as_rows(seqs[0]), None)
        assert assembled()[0][0] == b1 + 1.0


def test_handed_out_arrays_refuse_a_write():
    keys = valset(8, tag=4)
    with predecomp_sandbox():
        fill_by_hand(keys)
        for _ in range(2):
            handed = ed25519._predecomp_rows(as_rows(keys), None)
            mirror, idx = handed
            assert not idx.flags.writeable
            with pytest.raises(ValueError):
                idx[0] = 0
            with pytest.raises(TypeError):      # immutable by its type
                mirror[0] = 0
        rows_match_their_keys(handed, keys)
        # and the mirror is a copy: the table's next fill does not reach it
        before = np.asarray(mirror).tobytes()
        fill_by_hand(valset(8, tag=5))
        ed25519._predecomp.rows[:8] ^= 0xFF
        assert np.asarray(mirror).tobytes() == before


def test_with_a_mesh_the_rows_are_taken_on_the_host_before_the_shard():
    keys = valset(8, tag=6) * 2
    with predecomp_sandbox():
        fill_by_hand(set(keys))
        for _ in range(2):              # built, then reused
            handed = ed25519._predecomp_rows(as_rows(keys), object())
            rows, = handed
            assert type(rows) is np.ndarray and rows.shape == (16, 65)
            rows_match_their_keys(handed, keys)


def test_threads_asking_for_one_sequence_at_once_get_equal_rows():
    keys = valset(64, tag=5) * 8
    pk = as_rows(keys)
    n_threads, rounds = 16, 6
    got, errors = [], []
    gate = threading.Barrier(n_threads)

    def ask():
        try:
            for _ in range(rounds):
                gate.wait(timeout=30)
                got.append(ed25519._predecomp_rows(pk.copy(), None))
        except Exception as e:      # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with predecomp_sandbox():
            fill_by_hand(set(keys))
            (b0, r0), s0 = assembled()
            threads = [threading.Thread(target=ask) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads) and not errors
            (b1, r1), s1 = assembled()
            assert len(got) == n_threads * rounds
            want = [a.tobytes() for a in handed_rows(got[0])]
            for handed in got:
                assert [a.tobytes() for a in handed_rows(handed)] == want
            rows_match_their_keys(got[-1], keys)
            # every call was a hit, built or reused, and none was lost
            assert s1["hit"] - s0["hit"] == len(got)
            assert (b1 - b0) + (r1 - r0) == len(got) and r1 - r0 >= rounds - 1
            assert len(ed25519._predecomp_memo) == 1
    finally:
        sys.setswitchinterval(interval)


def two_key_lists_82_commits():
    """A follower's chunk where a key joined: 41 commits of one list of
    100 keys, 41 of the list with its 38th key replaced (8,200 lanes:
    the chunk takes 8,192 of them)."""
    before = valset(100, tag=7)
    after = before[:37] + valset(1, tag=8) + before[38:]
    return (before * 41 + after * 41)[:8192]


REAL_LAYOUTS = {
    "82_commits_over_two_key_lists": two_key_lists_82_commits,
    "8192_distinct": lambda: valset(8192, tag=9),
    # 60 commits of 100 keys and 2,192 padded lanes of the zero key
    "padded_zero_key_lanes": lambda: (valset(100, tag=7) * 60
                                      + [bytes(32)] * 2192),
}


@pytest.mark.parametrize("layout", sorted(REAL_LAYOUTS))
def test_rows_by_index_equal_decompress_row_for_row(layout):
    """The rows a dispatch computes on are byte-equal to what
    _decompress_to_bytes gives for each lane's key (half of these keys
    are no point: their rows carry ok False). The table is filled the
    way the program fills it, by the chunk's distinct keys in a batch
    of their own or (8,192 distinct) by the chunk itself; the chunk
    then resolves by the lookup alone."""
    keys = REAL_LAYOUTS[layout]()
    pk = as_rows(keys)
    distinct = sorted(set(keys))
    small = as_rows(distinct + [bytes(32)] * (-len(distinct) % 128))
    with predecomp_sandbox(min_batch=64):
        # sighted, then filled; or filled at once, where the padding
        # shows the zero key in several lanes
        repeats = small.shape[0] > len(
            {small[i].tobytes() for i in range(small.shape[0])})
        filled = ed25519._predecomp_rows(small, None)
        assert (filled is None) == (not repeats)
        if filled is None:
            filled = ed25519._predecomp_rows(small, None)
        assert ed25519.predecomp_stats()["keys"] == len(
            {small[i].tobytes() for i in range(small.shape[0])})
        want = [np.asarray(a) for a in ed25519._decompress_to_bytes(small)]
        for got, ref_rows in zip(handed_rows(filled), want):
            assert got.dtype == ref_rows.dtype
            assert got.tobytes() == ref_rows.tobytes()
        assert 0 < want[2].sum() < len(want[2])     # points and non-points
        # the reference, lane by lane, in plain Python
        at = {small[i].tobytes(): i for i in range(small.shape[0])}
        lane = [at[k] for k in keys]
        (b0, r0), s0 = assembled()
        i0, x0 = lanes()
        handed = ed25519._predecomp_rows(pk, None)
        for got, ref_rows in zip(handed_rows(handed), want):
            assert got.tobytes() == ref_rows[lane].tobytes()
        (b1, r1), s1 = assembled()
        assert (b1 - b0, r1 - r0) == (1.0, 0.0)
        assert s1["hit"] == s0["hit"] + 1 and s1["decompress"] == s0["decompress"]
        assert lanes() == (i0 + 8192, x0)


def test_two_resident_keys_sharing_the_lookups_prefix_both_resolve():
    """The first eight bytes propose a slot and all 32 decide: keys made
    to share them (2**32 tries for an adversary's pair) resolve each to
    its own row, by the walk, and the lanes that needed it are counted;
    a key that shares the prefix and is not resident is a miss."""
    plain = valset(29, tag=10)
    twins = [plain[3][:8] + hashlib.sha256(b"twin %d" % i).digest()[8:]
             for i in range(3)]                 # plain[3]'s prefix, four times
    stranger = plain[3][:8] + bytes(24)
    keys = plain + twins
    chunk = (keys * 4)[:128]
    with predecomp_sandbox():
        fill_by_hand(keys)
        i0, x0 = lanes()
        handed = ed25519._predecomp_rows(as_rows(chunk), None)
        rows_match_their_keys(handed, chunk)
        i1, x1 = lanes()
        # each key four times; the prefix proposes one of the four that
        # share it (which is the table's business): its lanes need no walk
        assert (i1 - i0, x1 - x0) == (128.0 - 12.0, 12.0)
        slot, miss, second = ed25519._predecomp.lookup(
            as_rows([stranger, twins[2], plain[0]]))
        assert miss.tolist() == [True, False, False] and second >= 1
    # a table of ONE prefix throughout, asked for a key it lacks too
    with predecomp_sandbox():
        same = [bytes(8) + hashlib.sha256(b"same %d" % i).digest()[8:]
                for i in range(16)]
        fill_by_hand(same)
        slot, miss, second = ed25519._predecomp.lookup(
            as_rows(same[::-1] + [bytes(32)]))
        assert miss.tolist() == [False] * 16 + [True] and second >= 16
        rows_match_their_keys(
            ed25519._predecomp_rows(as_rows(same[::-1]), None), same[::-1])


def test_a_fill_past_capacity_evicts_the_least_recently_stamped():
    """... and a dispatch taken before it still verifies right: it keeps
    the mirror its slots were resolved against."""
    a = signed_batch(15)
    b = signed_batch(16, n=4)
    half = [x[:4] * 2 for x in a]           # a's first four keys, twice
    newcomers = [x * 2 for x in b]          # four new keys, twice
    pk, rb, sb, hb, pre = ed25519.prepare_batch_bytes(*a)
    assert pre.all()
    with predecomp_sandbox(max_keys=8):
        for _ in range(2):                  # full, fill: a's eight resident
            assert ed25519.verify_batch(*a).all()
        taken = ed25519._predecomp_rows(pk, None)
        assert ed25519.verify_batch(*half).all()    # a hit: four stamped anew
        _, s0 = assembled()
        for _ in range(2):                  # full, then a fill past the room
            assert ed25519.verify_batch(*newcomers).all()
        _, s1 = assembled()
        assert s1["evict"] == s0["evict"] + 4 and s1["keys"] == 8
        assert all(resident(*a[0][:4], *b[0]))
        assert not any(resident(*a[0][4:]))
        # the table's slots hold other keys now; the dispatch taken
        # before the fill computes on the rows it was resolved against
        assert ed25519._predecomp.mirror() is not taken[0]
        got = ed25519._dispatch("pre", None, rb, sb, hb, *taken)
        assert np.asarray(got).tolist() == [True] * 8
        # and a's sequence, resolved an epoch ago, goes through fill
        assert ed25519.verify_batch(*a).all()
        _, s2 = assembled()
        assert s2["fill"] == s1["fill"] + 1


def test_slots_and_mirror_of_one_dispatch_are_of_one_table_under_fills(
        monkeypatch):
    """Threads asking for overlapping key sets through a table too small
    for all of them, so that fills and evictions run beside the lookups:
    whatever a call is handed, its mirror gathered at its slots is its
    keys' rows. The decompression is a stand-in (rows by row_of); the
    cache layer is under test."""
    pool = valset(40, tag=11)
    sets = [pool[i:i + 16] for i in (0, 8, 16, 24)]
    n_threads, rounds = 8, 40
    errors, served = [], []

    def fake_decompress(pk):
        rows = [row_of(pk[i].tobytes()) for i in range(pk.shape[0])]
        return (as_rows([r[0] for r in rows]), as_rows([r[1] for r in rows]),
                np.array([r[2] for r in rows]))

    def ask(t):
        try:
            for r in range(rounds):
                keys = sets[(t + r) % len(sets)]
                handed = ed25519._predecomp_rows(as_rows(keys), None)
                if handed is not None:      # None: a first sighting
                    rows_match_their_keys(handed, keys)
                    served.append(1)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    monkeypatch.setattr(ed25519, "_decompress_keys", fake_decompress)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with predecomp_sandbox(max_keys=24):
            _, s0 = assembled()
            threads = [threading.Thread(target=ask, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors[:1]
            _, s1 = assembled()
            assert len(served) >= n_threads * (rounds - len(sets))
            assert s1["evict"] > s0["evict"] and s1["fill"] > s0["fill"]
            assert s1["keys"] <= 24
    finally:
        sys.setswitchinterval(interval)
