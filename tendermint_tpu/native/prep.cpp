// Batched Ed25519 verify-prep — CPython extension.
//
// Takes the verifier's items list [(pubkey, msg, signature), ...] and
// produces the four device-bound arrays (pk[n,32], R[n,32], s[n,32],
// h[n,32]) plus the precheck mask in ONE call: classification, length
// checks, the s < L malleability check, h = SHA512(R||A||M) mod L —
// everything ops/ed25519.prepare_batch_bytes and the BatchVerifier
// dispatch loop otherwise do per item in Python. Replaces the host
// half of the reference's per-signature VerifyBytes surface
// (types/validator_set.go:240-265, go-crypto PubKeyEd25519.VerifyBytes).
//
// The SHA-512 loop runs with the GIL RELEASED over private copies of
// the inputs, so a node pipelining several commits overlaps hashing
// with device fetches; given `threads` > 1 it runs over that many
// contiguous shards of the batch at once (hash_lanes: threads that
// live inside the one call). SHA-512 itself is OpenSSL's when
// libcrypto.so.3 is loadable at runtime (AVX2 assembly, ~3x the
// portable block function) and the portable Sha512 from hostops.cpp
// otherwise.
//
// Returns None for input shapes the fast path does not cover —
// secp256k1 keys (33-byte SEC1, host-verified by design), non-bytes
// entries — and the Python wrapper then takes the general path, so
// this extension can never change routing semantics, only speed.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <dlfcn.h>

#include <system_error>
#include <thread>

#include "hostops.cpp"

namespace {

// OpenSSL's streaming SHA-512 (SHA512_Init/Update/Final), not its
// one-shot SHA512(): since 3.0 the one-shot fetches the algorithm on
// every call, which costs as much again as hashing a 346-byte input
// (32,768 inputs: 43 ms against 25-27 on the sandbox's CPU, PR 27), and
// the streaming calls take R, A and M where they lie.
typedef int (*sha512_init_fn)(void *);
typedef int (*sha512_update_fn)(void *, const void *, size_t);
typedef int (*sha512_final_fn)(unsigned char *, void *);
sha512_init_fn ossl_init = nullptr;
sha512_update_fn ossl_update = nullptr;
sha512_final_fn ossl_final = nullptr;

inline void sha512_ram(const uint8_t *r, const uint8_t *a,
                       const uint8_t *m, size_t mlen, uint8_t out[64]) {
    // SHA512(r32 || a32 || M); a may be null (32-byte-prefix inputs —
    // the signing nonce hash SHA512(prefix || M))
    if (ossl_final != nullptr) {
        alignas(16) uint8_t ctx[512];   // SHA512_CTX is 216 bytes
        ossl_init(ctx);
        ossl_update(ctx, r, 32);
        if (a != nullptr) ossl_update(ctx, a, 32);
        ossl_update(ctx, m, mlen);
        ossl_final(out, ctx);
        return;
    }
    Sha512 s;
    s.update(r, 32);
    if (a != nullptr) s.update(a, 32);
    s.update(m, mlen);
    s.final(out);
}

}  // namespace

namespace {

// The five outputs of a verify prep (pk, R, s, h as n*32 bytes, pre as
// n bytes), zeroed: a lane that fails its precheck stays zero.
struct PrepOut {
    PyObject *obj[5] = {nullptr, nullptr, nullptr, nullptr, nullptr};
    uint8_t *pk = nullptr, *rb = nullptr, *sb = nullptr, *hb = nullptr,
            *pre = nullptr;

    bool alloc(Py_ssize_t n) {
        for (int k = 0; k < 5; k++) {
            Py_ssize_t len = k < 4 ? n * 32 : n;
            obj[k] = PyBytes_FromStringAndSize(nullptr, len);
            if (obj[k] == nullptr) return false;
            std::memset(PyBytes_AS_STRING(obj[k]), 0, (size_t)len);
        }
        pk = (uint8_t *)PyBytes_AS_STRING(obj[0]);
        rb = (uint8_t *)PyBytes_AS_STRING(obj[1]);
        sb = (uint8_t *)PyBytes_AS_STRING(obj[2]);
        hb = (uint8_t *)PyBytes_AS_STRING(obj[3]);
        pre = (uint8_t *)PyBytes_AS_STRING(obj[4]);
        return true;
    }

    // Lane i's precheck (key and signature lengths, s < L); a lane that
    // passes gets its pk, R and s rows and pre = 1.
    bool admit(Py_ssize_t i, const uint8_t *key, Py_ssize_t klen,
               const uint8_t *sig, Py_ssize_t slen) {
        if (klen != 32 || slen != 64 || !scalar_below_l(sig + 32))
            return false;
        std::memcpy(pk + 32 * i, key, 32);
        std::memcpy(rb + 32 * i, sig, 32);
        std::memcpy(sb + 32 * i, sig + 32, 32);
        pre[i] = 1;
        return true;
    }

    PyObject *pack() {
        PyObject *out =
            PyTuple_Pack(5, obj[0], obj[1], obj[2], obj[3], obj[4]);
        drop();
        return out;
    }

    void drop() {
        for (int k = 0; k < 5; k++) Py_CLEAR(obj[k]);
    }
};

// Pass 2 of a verify prep, GIL released: h = SHA512(R || A || M) mod L
// for every lane that passed its precheck; msg_of(i) gives lane i's
// message where it lies. With threads > 1, [0, n) is cut into that many
// equal contiguous shards, each hashed by a std::thread of its own, and
// all are joined before this returns: no pool and no thread that
// outlives the call, so the process forks as safely after a prep as
// before it. A lane reads its own inputs and writes its own row of hb;
// the shards share nothing else. A shard whose thread cannot be started
// is hashed here, on the calling thread.
template <class MsgOf>
void hash_lanes(const PrepOut &out, Py_ssize_t n, long threads,
                const MsgOf &msg_of) {
    auto shard = [&](Py_ssize_t lo, Py_ssize_t hi) {
        for (Py_ssize_t i = lo; i < hi; i++) {
            if (!out.pre[i]) continue;
            uint8_t digest[64];
            size_t mlen;
            const uint8_t *m = msg_of(i, &mlen);
            sha512_ram(out.rb + 32 * i, out.pk + 32 * i, m, mlen, digest);
            reduce512_mod_l(digest, out.hb + 32 * i);
        }
    };
    if (threads > n) threads = (long)n;
    if (threads <= 1) {
        shard(0, n);
        return;
    }
    std::vector<std::thread> workers;
    workers.reserve((size_t)threads);
    for (long t = 0; t < threads; t++) {
        Py_ssize_t lo = n * t / threads, hi = n * (t + 1) / threads;
        try {
            workers.emplace_back(shard, lo, hi);
        } catch (const std::system_error &) {
            shard(lo, hi);
        }
    }
    for (std::thread &w : workers) w.join();
}

}  // namespace

static PyObject *prep_items(PyObject *self, PyObject *args) {
    PyObject *arg;
    long threads = 1;
    if (!PyArg_ParseTuple(args, "O|l", &arg, &threads)) return nullptr;
    PyObject *seq = PySequence_Fast(arg, "prep_items expects a sequence");
    if (seq == nullptr) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);

    PrepOut out;
    if (!out.alloc(n)) {
        out.drop();
        Py_DECREF(seq);
        return nullptr;
    }

    // Pass 1 (GIL held): copy messages into a private arena and pk/R/s
    // into the output buffers. Copies make the hash loop independent of
    // object lifetimes, so the GIL can drop for pass 2.
    std::vector<uint8_t> arena;
    arena.reserve((size_t)n * 160);
    std::vector<uint64_t> moff((size_t)n + 1, 0);
    bool fallback = false;
    for (Py_ssize_t i = 0; i < n && !fallback; i++) {
        PyObject *it = PySequence_Fast_GET_ITEM(seq, i);
        PyObject *fast =
            PySequence_Fast(it, "prep_items items must be sequences");
        if (fast == nullptr) {
            PyErr_Clear();
            fallback = true;
            break;
        }
        if (PySequence_Fast_GET_SIZE(fast) != 3) {
            Py_DECREF(fast);
            fallback = true;
            break;
        }
        PyObject *po = PySequence_Fast_GET_ITEM(fast, 0);
        PyObject *mo = PySequence_Fast_GET_ITEM(fast, 1);
        PyObject *so = PySequence_Fast_GET_ITEM(fast, 2);
        if (!PyBytes_Check(po) || !PyBytes_Check(mo) || !PyBytes_Check(so)) {
            Py_DECREF(fast);
            fallback = true;  // memoryview/bytearray etc: general path
            break;
        }
        Py_ssize_t plen = PyBytes_GET_SIZE(po);
        const uint8_t *pp = (const uint8_t *)PyBytes_AS_STRING(po);
        if (plen == 33 && (pp[0] == 2 || pp[0] == 3)) {
            Py_DECREF(fast);
            fallback = true;  // secp256k1: host-routed, general path
            break;
        }
        moff[i + 1] = moff[i];
        if (out.admit(i, pp, plen, (const uint8_t *)PyBytes_AS_STRING(so),
                      PyBytes_GET_SIZE(so))) {
            Py_ssize_t mlen = PyBytes_GET_SIZE(mo);
            const uint8_t *mp = (const uint8_t *)PyBytes_AS_STRING(mo);
            arena.insert(arena.end(), mp, mp + mlen);
            moff[i + 1] = moff[i] + (uint64_t)mlen;
        }
        Py_DECREF(fast);
    }
    Py_DECREF(seq);
    if (fallback) {
        out.drop();
        Py_RETURN_NONE;
    }

    Py_BEGIN_ALLOW_THREADS
    hash_lanes(out, n, threads, [&](Py_ssize_t i, size_t *mlen) {
        *mlen = (size_t)(moff[i + 1] - moff[i]);
        return (const uint8_t *)arena.data() + moff[i];
    });
    Py_END_ALLOW_THREADS
    return out.pack();
}

// prep_columns(pk, sigs, msgs, idx[, threads]): prep_items for a batch
// held as columns (types/sigcolumns.py) — pk a contiguous n*32-byte buffer, sigs
// a sequence of n bytes objects, msgs the batch's sign-bytes and idx a
// contiguous int32[n] buffer naming each lane's. The same prechecks and
// the same five arrays, bit for bit; the columns are read where they
// lie (the references taken in pass 1 keep the messages alive while the
// GIL is released). None where a member is not bytes: general path.
static PyObject *prep_columns(PyObject *, PyObject *args) {
    Py_buffer pkv, idxv;
    PyObject *sigs_o, *msgs_o;
    long threads = 1;
    if (!PyArg_ParseTuple(args, "y*OOy*|l", &pkv, &sigs_o, &msgs_o, &idxv,
                          &threads))
        return nullptr;
    PyObject *sigs = nullptr, *msgs = nullptr, *result = nullptr;
    PrepOut out;
    std::vector<PyObject *> held;    // the messages, a reference each
    Py_ssize_t n = 0, n_msgs = 0;
    const uint8_t *keys = (const uint8_t *)pkv.buf;
    const int32_t *idx = (const int32_t *)idxv.buf;
    bool fallback = false;

    sigs = PySequence_Fast(sigs_o, "prep_columns: sigs must be a sequence");
    if (sigs == nullptr) goto done;
    msgs = PySequence_Fast(msgs_o, "prep_columns: msgs must be a sequence");
    if (msgs == nullptr) goto done;
    n = PySequence_Fast_GET_SIZE(sigs);
    n_msgs = PySequence_Fast_GET_SIZE(msgs);
    if (pkv.len != 32 * n || idxv.len != 4 * n) {
        PyErr_SetString(PyExc_ValueError,
                        "prep_columns: pk must be n*32 bytes and idx n "
                        "int32 for n signatures");
        goto done;
    }
    if (!out.alloc(n)) goto done;

    // Pass 1 (GIL held): the prechecks, pk/R/s into the output buffers
    held.reserve((size_t)n_msgs);
    for (Py_ssize_t k = 0; k < n_msgs; k++) {
        PyObject *mo = PySequence_Fast_GET_ITEM(msgs, k);
        if (!PyBytes_Check(mo)) {
            fallback = true;
            break;
        }
        Py_INCREF(mo);
        held.push_back(mo);
    }
    for (Py_ssize_t i = 0; i < n && !fallback; i++) {
        PyObject *so = PySequence_Fast_GET_ITEM(sigs, i);
        if (!PyBytes_Check(so)) {
            fallback = true;
            break;
        }
        if (idx[i] < 0 || idx[i] >= n_msgs) {
            PyErr_SetString(PyExc_ValueError,
                            "prep_columns: idx names no message");
            goto release;
        }
        out.admit(i, keys + 32 * i, 32,
                  (const uint8_t *)PyBytes_AS_STRING(so),
                  PyBytes_GET_SIZE(so));
    }
    if (fallback) {
        result = Py_None;
        Py_INCREF(result);
        goto release;
    }

    Py_BEGIN_ALLOW_THREADS
    hash_lanes(out, n, threads, [&](Py_ssize_t i, size_t *mlen) {
        PyObject *mo = held[idx[i]];
        *mlen = (size_t)PyBytes_GET_SIZE(mo);
        return (const uint8_t *)PyBytes_AS_STRING(mo);
    });
    Py_END_ALLOW_THREADS
    result = out.pack();

release:
    for (PyObject *mo : held) Py_DECREF(mo);
done:
    out.drop();
    Py_XDECREF(sigs);
    Py_XDECREF(msgs);
    PyBuffer_Release(&pkv);
    PyBuffer_Release(&idxv);
    return result;
}

namespace {

// s = (r + k*a) mod L. r and k are < L; a is the CLAMPED secret
// scalar (bit 254 set, so a >= 2^254 > L — not reduced). The product
// goes through the general 512-bit reduction, which needs no bound
// beyond < 2^512; only the final r + (k*a mod L) sum relies on < L.
inline void muladd_mod_l(const uint8_t r[32], const uint8_t k[32],
                         const uint8_t a[32], uint8_t out[32]) {
    uint64_t kl[4], al[4];
    for (int i = 0; i < 4; i++) {
        uint64_t kw = 0, aw = 0;
        for (int j = 7; j >= 0; j--) {
            kw = (kw << 8) | k[8 * i + j];
            aw = (aw << 8) | a[8 * i + j];
        }
        kl[i] = kw;
        al[i] = aw;
    }
    // 4x4 schoolbook -> 8 limbs
    uint64_t prod[8] = {0};
    for (int i = 0; i < 4; i++) {
        unsigned __int128 carry = 0;
        for (int j = 0; j < 4; j++) {
            carry += (unsigned __int128)kl[i] * al[j] + prod[i + j];
            prod[i + j] = (uint64_t)carry;
            carry >>= 64;
        }
        prod[i + 4] = (uint64_t)carry;
    }
    uint8_t prod_le[64];
    for (int i = 0; i < 8; i++)
        for (int j = 0; j < 8; j++)
            prod_le[8 * i + j] = uint8_t(prod[i] >> (8 * j));
    uint8_t ka[32];
    reduce512_mod_l(prod_le, ka);
    // out = r + ka, minus L if the sum reaches it (both inputs < L)
    unsigned carry = 0;
    for (int i = 0; i < 32; i++) {
        unsigned t = (unsigned)r[i] + ka[i] + carry;
        out[i] = uint8_t(t);
        carry = t >> 8;
    }
    if (carry || !scalar_below_l(out)) {
        uint8_t l_bytes[32];
        for (int i = 0; i < 4; i++)
            for (int j = 0; j < 8; j++)
                l_bytes[8 * i + j] = uint8_t(L_LIMBS[i] >> (8 * j));
        unsigned borrow = 0;
        for (int i = 0; i < 32; i++) {
            int t = (int)out[i] - l_bytes[i] - (int)borrow;
            out[i] = uint8_t(t & 0xFF);
            borrow = t < 0;
        }
    }
}

}  // namespace

// sign_phase1(prefixes n*32, msgs) -> r bytes n*32:
// r = SHA512(prefix || M) mod L (RFC 8032 nonce). GIL released.
static PyObject *sign_phase1(PyObject *, PyObject *args) {
    const char *pre;
    Py_ssize_t pre_len;
    PyObject *msgs;
    if (!PyArg_ParseTuple(args, "y#O", &pre, &pre_len, &msgs))
        return nullptr;
    PyObject *seq = PySequence_Fast(msgs, "sign_phase1 expects msgs");
    if (seq == nullptr) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (pre_len != 32 * n) {
        Py_DECREF(seq);
        PyErr_SetString(PyExc_ValueError, "prefixes must be n*32 bytes");
        return nullptr;
    }
    // the y# blob pointers borrow from immutable bytes held by the
    // call's argument tuple — valid for the whole call, GIL or not;
    // only the msgs (many objects) need aggregating into an arena
    std::vector<uint8_t> arena;
    std::vector<uint64_t> off((size_t)n + 1, 0);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *m = PySequence_Fast_GET_ITEM(seq, i);
        if (!PyBytes_Check(m)) {
            Py_DECREF(seq);
            PyErr_SetString(PyExc_TypeError, "msgs must be bytes");
            return nullptr;
        }
        const uint8_t *p = (const uint8_t *)PyBytes_AS_STRING(m);
        arena.insert(arena.end(), p, p + PyBytes_GET_SIZE(m));
        off[i + 1] = off[i] + (uint64_t)PyBytes_GET_SIZE(m);
    }
    Py_DECREF(seq);
    PyObject *out_b = PyBytes_FromStringAndSize(nullptr, n * 32);
    if (out_b == nullptr) return nullptr;
    uint8_t *out = (uint8_t *)PyBytes_AS_STRING(out_b);
    const uint8_t *prefixes = (const uint8_t *)pre;
    Py_BEGIN_ALLOW_THREADS
    for (Py_ssize_t i = 0; i < n; i++) {
        uint8_t digest[64];
        sha512_ram(prefixes + 32 * i, nullptr,
                   arena.data() + off[i], (size_t)(off[i + 1] - off[i]),
                   digest);
        reduce512_mod_l(digest, out + 32 * i);
    }
    Py_END_ALLOW_THREADS
    return out_b;
}

// sign_phase2(renc n*32, pks n*32, msgs, r n*32, a n*32) -> sigs n*64:
// k = SHA512(Renc || A || M) mod L; s = (r + k*a) mod L; sig = Renc||s.
static PyObject *sign_phase2(PyObject *, PyObject *args) {
    const char *renc, *pks, *rs, *as_;
    Py_ssize_t renc_len, pks_len, rs_len, as_len;
    PyObject *msgs;
    if (!PyArg_ParseTuple(args, "y#y#Oy#y#", &renc, &renc_len, &pks,
                          &pks_len, &msgs, &rs, &rs_len, &as_, &as_len))
        return nullptr;
    PyObject *seq = PySequence_Fast(msgs, "sign_phase2 expects msgs");
    if (seq == nullptr) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (renc_len != 32 * n || pks_len != 32 * n || rs_len != 32 * n ||
        as_len != 32 * n) {
        Py_DECREF(seq);
        PyErr_SetString(PyExc_ValueError, "scalar blobs must be n*32");
        return nullptr;
    }
    std::vector<uint8_t> arena;
    std::vector<uint64_t> off((size_t)n + 1, 0);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *m = PySequence_Fast_GET_ITEM(seq, i);
        if (!PyBytes_Check(m)) {
            Py_DECREF(seq);
            PyErr_SetString(PyExc_TypeError, "msgs must be bytes");
            return nullptr;
        }
        const uint8_t *p = (const uint8_t *)PyBytes_AS_STRING(m);
        arena.insert(arena.end(), p, p + PyBytes_GET_SIZE(m));
        off[i + 1] = off[i] + (uint64_t)PyBytes_GET_SIZE(m);
    }
    Py_DECREF(seq);
    // borrowed blob pointers (see sign_phase1) — no defensive copies
    const uint8_t *rc = (const uint8_t *)renc;
    const uint8_t *pc = (const uint8_t *)pks;
    const uint8_t *rv = (const uint8_t *)rs;
    const uint8_t *av = (const uint8_t *)as_;
    PyObject *out_b = PyBytes_FromStringAndSize(nullptr, n * 64);
    if (out_b == nullptr) return nullptr;
    uint8_t *out = (uint8_t *)PyBytes_AS_STRING(out_b);
    Py_BEGIN_ALLOW_THREADS
    for (Py_ssize_t i = 0; i < n; i++) {
        uint8_t digest[64], k[32];
        sha512_ram(rc + 32 * i, pc + 32 * i,
                   arena.data() + off[i], (size_t)(off[i + 1] - off[i]),
                   digest);
        reduce512_mod_l(digest, k);
        std::memcpy(out + 64 * i, rc + 32 * i, 32);
        muladd_mod_l(rv + 32 * i, k, av + 32 * i, out + 64 * i + 32);
    }
    Py_END_ALLOW_THREADS
    return out_b;
}

// merkle_root_items(list[bytes]) -> 32-byte root. Same spec as
// tm_merkle_root, but taking the Python list directly: the ctypes
// wrapper's per-item offset packing costs more than the hashing for
// the 5,000-leaf tx trees the sync loop validates per block. Items are
// copied to a private arena so the hash loop can drop the GIL.
static PyObject *merkle_root_items(PyObject *self, PyObject *arg) {
    PyObject *seq = PySequence_Fast(arg, "merkle_root_items expects a list");
    if (seq == nullptr) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    std::vector<uint8_t> arena;
    std::vector<uint64_t> off((size_t)n + 1, 0);
    arena.reserve((size_t)n * 32);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *it = PySequence_Fast_GET_ITEM(seq, i);
        if (!PyBytes_Check(it)) {
            Py_DECREF(seq);
            PyErr_SetString(PyExc_TypeError,
                            "merkle_root_items: items must be bytes");
            return nullptr;
        }
        const uint8_t *p = (const uint8_t *)PyBytes_AS_STRING(it);
        Py_ssize_t len = PyBytes_GET_SIZE(it);
        arena.insert(arena.end(), p, p + len);
        off[i + 1] = off[i] + (uint64_t)len;
    }
    Py_DECREF(seq);
    uint8_t out[32];
    Py_BEGIN_ALLOW_THREADS
    tm_merkle_root(arena.data(), off.data(), (uint64_t)n, out);
    Py_END_ALLOW_THREADS
    return PyBytes_FromStringAndSize((const char *)out, 32);
}

namespace {

// A reference that is dropped at the end of its scope.
struct Ref {
    PyObject *o;
    explicit Ref(PyObject *p = nullptr) : o(p) {}
    Ref(const Ref &) = delete;
    Ref &operator=(const Ref &) = delete;
    ~Ref() { Py_XDECREF(o); }
    void take(PyObject *p) {    // steals p
        Py_XDECREF(o);
        o = p;
    }
    void share(PyObject *p) {   // p stays its owner's too
        Py_INCREF(p);
        take(p);
    }
};

// The attribute names of the vote walk, interned once at import.
PyObject *s_type, *s_height, *s_round, *s_block_id, *s_timestamp_ns,
    *s_signature, *s_hash, *s_parts, *s_total;

// What became of a read: the value, an error that is set, or something
// the walk does not read as expected, on which it returns None and the
// Python loop judges the commit.
enum Read { GOT, RAISED, DECLINED };

// o.<name> by getattr. A missing attribute declines: which of several
// faults of a vote is reported is the loop's to say. (Reading a plain
// Vote's fields from its __dict__ instead was tried, PR 44: on 3.12
// asking for the dict builds it, which costs more than it saves.)
inline Read field(PyObject *o, PyObject *name, Ref &out) {
    out.take(PyObject_GetAttr(o, name));
    if (out.o != nullptr) return GOT;
    if (!PyErr_ExceptionMatches(PyExc_AttributeError)) return RAISED;
    PyErr_Clear();
    return DECLINED;
}

// An `int` itself (no subclass, so no bool) that an int64 holds.
inline bool as_i64(PyObject *o, long long *v) {
    if (!PyLong_CheckExact(o)) return false;
    int overflow = 0;
    *v = PyLong_AsLongLongAndOverflow(o, &overflow);
    return overflow == 0 && !(*v == -1 && PyErr_Occurred());
}

inline Read int_field(PyObject *o, PyObject *name, long long *v) {
    Ref r;
    Read got = field(o, name, r);
    if (got != GOT) return got;
    if (as_i64(r.o, v)) return GOT;
    return PyErr_Occurred() ? RAISED : DECLINED;
}

inline bool same_bytes(PyObject *a, PyObject *b) {
    return a == b ||
           (PyBytes_GET_SIZE(a) == PyBytes_GET_SIZE(b) &&
            std::memcmp(PyBytes_AS_STRING(a), PyBytes_AS_STRING(b),
                        (size_t)PyBytes_GET_SIZE(a)) == 0);
}

// prefix + decimal(ts) + suffix as a new bytes object.
PyObject *splice(const char *pre, Py_ssize_t pre_n, long long ts,
                 const char *suf, Py_ssize_t suf_n) {
    char digits[24];
    char *end = digits + sizeof digits, *p = end;
    unsigned long long mag =
        ts < 0 ? 0ULL - (unsigned long long)ts : (unsigned long long)ts;
    do {
        *--p = char('0' + mag % 10);
        mag /= 10;
    } while (mag != 0);
    if (ts < 0) *--p = '-';
    Py_ssize_t dig_n = end - p;
    PyObject *out =
        PyBytes_FromStringAndSize(nullptr, pre_n + dig_n + suf_n);
    if (out == nullptr) return nullptr;
    char *w = PyBytes_AS_STRING(out);
    std::memcpy(w, pre, (size_t)pre_n);
    std::memcpy(w + pre_n, p, (size_t)dig_n);
    std::memcpy(w + pre_n + dig_n, suf, (size_t)suf_n);
    return out;
}

}  // namespace

// walk_votes(pcs, height, round, precommit, template) — THE vote
// walk of ValidatorSet.commit_verification_items (types/
// validator_set.py, whose `_walk_votes` is the specification: the same
// checks in the same order with the same ValueError texts, the same
// runs). `pcs`: a commit's precommits, None where a vote is absent.
// `template(block_id)` -> (prefix str, suffix str, for_block), asked
// once per distinct block id (identity first, then hash / parts.total /
// parts.hash): the sign-bytes layout stays Python's, this only splices
// each distinct timestamp's decimal between the two.
// -> (sigs: the votes' own signature objects, msgs: a sign-bytes per
// run of votes that signed the same, idx: int32[n] lane -> msgs as
// bytes, for_block: bool[n] as bytes, absent: the empty slots,
// all_for: every lane's vote is for the block), or None, never a guess,
// for whatever is not read as expected: pcs not a list, a field that is
// missing, an int field that is no `int` or fits no int64, a hash or
// signature that is no `bytes`, a template of another shape.
static PyObject *walk_votes(PyObject *, PyObject *args) {
    PyObject *pcs, *height_o, *round_o, *precommit_o, *tmpl;
    if (!PyArg_ParseTuple(args, "OOOOO", &pcs, &height_o, &round_o,
                          &precommit_o, &tmpl))
        return nullptr;
    long long height, round_, precommit;
    if (!PyList_CheckExact(pcs) || !as_i64(height_o, &height) ||
        !as_i64(round_o, &round_) || !as_i64(precommit_o, &precommit)) {
        if (PyErr_Occurred()) return nullptr;
        Py_RETURN_NONE;
    }
    Ref sigs(PyList_New(0)), msgs(PyList_New(0)), absent(PyList_New(0));
    if (sigs.o == nullptr || msgs.o == nullptr || absent.o == nullptr)
        return nullptr;
    std::vector<int32_t> idx;
    std::vector<uint8_t> flags;
    idx.reserve((size_t)PyList_GET_SIZE(pcs));
    flags.reserve((size_t)PyList_GET_SIZE(pcs));

    Ref known;                      // block id fields -> its template
    Ref bid, bhash, phash, cur;     // the current block id, its template
    long long ptotal = 0, ts = 0;
    bool have_ts = false, for_block = false, all_for = true;
    const char *pre = nullptr, *suf = nullptr;
    Py_ssize_t pre_n = 0, suf_n = 0;
    int32_t n_msgs = 0;
    Read got;
#define READ(call)                                   \
    if ((got = (call)) != GOT) {                     \
        if (got == RAISED) return nullptr;           \
        Py_RETURN_NONE;                              \
    }

    // the size is read anew each turn: template() runs Python
    for (Py_ssize_t i = 0; i < PyList_GET_SIZE(pcs); i++) {
        Ref pc;
        pc.share(PyList_GET_ITEM(pcs, i));
        if (pc.o == Py_None) {
            Ref at(PyLong_FromSsize_t(i));
            if (at.o == nullptr || PyList_Append(absent.o, at.o) < 0)
                return nullptr;
            continue;
        }
        long long v, w;
        READ(int_field(pc.o, s_type, &v));
        if (v != precommit) {
            PyErr_SetString(PyExc_ValueError,
                            "commit contains non-precommit");
            return nullptr;
        }
        READ(int_field(pc.o, s_height, &v));
        READ(int_field(pc.o, s_round, &w));
        if (v != height || w != round_) {
            PyErr_SetString(PyExc_ValueError,
                            "commit vote height/round mismatch");
            return nullptr;
        }
        Ref b;
        READ(field(pc.o, s_block_id, b));
        if (b.o != bid.o) {
            bid.share(b.o);
            Ref parts, h, ph;
            long long total;
            READ(field(b.o, s_parts, parts));
            READ(field(b.o, s_hash, h));
            READ(int_field(parts.o, s_total, &total));
            READ(field(parts.o, s_hash, ph));
            if (!PyBytes_CheckExact(h.o) || !PyBytes_CheckExact(ph.o))
                Py_RETURN_NONE;
            if (bhash.o == nullptr || total != ptotal ||
                !same_bytes(h.o, bhash.o) || !same_bytes(ph.o, phash.o)) {
                bhash.share(h.o);
                phash.share(ph.o);
                ptotal = total;
                if (known.o == nullptr) {
                    known.take(PyDict_New());
                    if (known.o == nullptr) return nullptr;
                }
                Ref total_o(PyLong_FromLongLong(total));
                if (total_o.o == nullptr) return nullptr;
                Ref key(PyTuple_Pack(3, h.o, total_o.o, ph.o));
                if (key.o == nullptr) return nullptr;
                PyObject *t = PyDict_GetItemWithError(known.o, key.o);
                if (t != nullptr) {
                    cur.share(t);
                } else {
                    if (PyErr_Occurred()) return nullptr;
                    cur.take(PyObject_CallOneArg(tmpl, b.o));
                    if (cur.o == nullptr) return nullptr;
                    if (!PyTuple_CheckExact(cur.o) ||
                        PyTuple_GET_SIZE(cur.o) != 3 ||
                        !PyUnicode_CheckExact(PyTuple_GET_ITEM(cur.o, 0)) ||
                        !PyUnicode_CheckExact(PyTuple_GET_ITEM(cur.o, 1)))
                        Py_RETURN_NONE;
                    if (PyDict_SetItem(known.o, key.o, cur.o) < 0)
                        return nullptr;
                }
                // the str keeps its UTF-8 form for as long as it lives,
                // and `cur` holds it
                pre = PyUnicode_AsUTF8AndSize(PyTuple_GET_ITEM(cur.o, 0),
                                              &pre_n);
                suf = PyUnicode_AsUTF8AndSize(PyTuple_GET_ITEM(cur.o, 1),
                                              &suf_n);
                int truth = PyObject_IsTrue(PyTuple_GET_ITEM(cur.o, 2));
                if (pre == nullptr || suf == nullptr || truth < 0)
                    return nullptr;
                for_block = truth != 0;
                all_for = all_for && for_block;
                have_ts = false;    // other sign-bytes too
            }
        }
        READ(int_field(pc.o, s_timestamp_ns, &v));
        if (!have_ts || v != ts) {
            ts = v;
            have_ts = true;
            Ref msg(splice(pre, pre_n, ts, suf, suf_n));
            if (msg.o == nullptr || PyList_Append(msgs.o, msg.o) < 0)
                return nullptr;
            n_msgs++;
        }
        Ref sig;
        READ(field(pc.o, s_signature, sig));
        if (!PyBytes_CheckExact(sig.o)) Py_RETURN_NONE;
        if (PyList_Append(sigs.o, sig.o) < 0) return nullptr;
        idx.push_back(n_msgs - 1);
        flags.push_back(for_block ? 1 : 0);
    }
#undef READ
    Ref idx_b(PyBytes_FromStringAndSize((const char *)idx.data(),
                                        (Py_ssize_t)(4 * idx.size())));
    Ref flags_b(PyBytes_FromStringAndSize((const char *)flags.data(),
                                          (Py_ssize_t)flags.size()));
    if (idx_b.o == nullptr || flags_b.o == nullptr) return nullptr;
    return Py_BuildValue("(OOOOOO)", sigs.o, msgs.o, idx_b.o, flags_b.o,
                         absent.o, all_for ? Py_True : Py_False);
}

static PyMethodDef prep_methods[] = {
    {"sign_phase1", sign_phase1, METH_VARARGS,
     "(prefixes n*32, msgs) -> r scalars n*32 (RFC 8032 nonces mod L)"},
    {"sign_phase2", sign_phase2, METH_VARARGS,
     "(renc n*32, pks n*32, msgs, r n*32, a n*32) -> signatures n*64"},
    {"merkle_root_items", merkle_root_items, METH_O,
     "list[bytes] -> 32-byte merkle root (same spec as ops/merkle)"},
    {"prep_items", prep_items, METH_VARARGS,
     "(items [(pk, msg, sig), ...], threads=1) -> (pk, R, s, h, pre) byte "
     "buffers, or None when the batch needs the general Python path; the "
     "hashing runs on `threads` threads that end with the call."},
    {"prep_columns", prep_columns, METH_VARARGS,
     "(pk n*32, sigs, msgs, idx int32[n], threads=1) -> what prep_items "
     "returns for the triples (pk[i], msgs[idx[i]], sigs[i])."},
    {"walk_votes", walk_votes, METH_VARARGS,
     "(pcs, height, round, precommit, template) -> (sigs, msgs, "
     "idx int32 bytes, for_block bool bytes, absent, all_for), or None "
     "where the Python loop must judge the commit."},
    {nullptr, nullptr, 0, nullptr},
};

static struct PyModuleDef prep_moduledef = {
    PyModuleDef_HEAD_INIT, "_tmprep",
    "Native batched Ed25519 verify-prep for tendermint_tpu", -1,
    prep_methods,
};

PyMODINIT_FUNC PyInit__tmprep(void) {
    void *crypto = dlopen("libcrypto.so.3", RTLD_LAZY | RTLD_LOCAL);
    if (crypto != nullptr) {
        ossl_init = (sha512_init_fn)dlsym(crypto, "SHA512_Init");
        ossl_update = (sha512_update_fn)dlsym(crypto, "SHA512_Update");
        if (ossl_init != nullptr && ossl_update != nullptr)
            ossl_final = (sha512_final_fn)dlsym(crypto, "SHA512_Final");
    }
    struct { PyObject **at; const char *name; } names[] = {
        {&s_type, "type"}, {&s_height, "height"}, {&s_round, "round"},
        {&s_block_id, "block_id"}, {&s_timestamp_ns, "timestamp_ns"},
        {&s_signature, "signature"}, {&s_hash, "hash"},
        {&s_parts, "parts"}, {&s_total, "total"},
    };
    for (auto &n : names) {
        *n.at = PyUnicode_InternFromString(n.name);
        if (*n.at == nullptr) return nullptr;
    }
    PyObject *m = PyModule_Create(&prep_moduledef);
    if (m != nullptr)
        PyModule_AddStringConstant(
            m, "sha512_impl", ossl_final ? "openssl" : "portable");
    return m;
}
