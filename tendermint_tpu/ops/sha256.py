"""Batched SHA-256 in pure jnp uint32 — the hash plane of the framework.

The reference builds RIPEMD160 Merkle trees node-at-a-time on the CPU
(types/tx.go:33-46, types/part_set.go:110 via tmlibs/merkle). This rebuild
standardizes on SHA-256 (a deliberate TPU-first divergence: SHA-256 is pure
32-bit logic that vectorizes perfectly on the VPU, and is the modern choice
— later Tendermint versions made the same move off RIPEMD160).

Everything is fixed-shape: hashing M bytes requires M static, which is the
natural shape discipline for XLA and exactly how the Merkle plane uses it
(leaves and inner nodes have known sizes). Variable-length host-side
hashing stays on hashlib.

All functions broadcast over leading batch dims; words are uint32 (mod-2^32
adds wrap natively), bytes are uint8.
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2], dtype=np.uint32)

IV = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19], dtype=np.uint32)


def _rotr(x, n):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def compress(state, block):
    """One SHA-256 compression: state uint32[...,8], block uint32[...,16].

    The 48 schedule steps and 64 rounds run under lax.fori_loop, NOT
    unrolled: a Merkle program hashes at every tree level, and fully
    unrolled rounds made the 8-way-SPMD tree compile pathological on
    XLA:CPU (>10 min, tens of GB of compiler RSS — an O(ops²) pass).
    Looped rounds keep every hash ~60x smaller in the HLO. The round
    body is elementwise over the batch, so on TPU the loop overhead
    amortizes across lanes; each level is still one wide VPU batch."""
    w = jnp.concatenate(
        [block, jnp.zeros(block.shape[:-1] + (48,), jnp.uint32)], axis=-1)

    def sched(t, w):
        take = lambda off: jax.lax.dynamic_index_in_dim(
            w, t - off, axis=-1, keepdims=False)
        w15, w2, w16, w7 = take(15), take(2), take(16), take(7)
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
        return jax.lax.dynamic_update_index_in_dim(
            w, w16 + s0 + w7 + s1, t, axis=-1)

    w = jax.lax.fori_loop(16, 64, sched, w)
    k_const = jnp.asarray(_K)

    def round_(t, carry):
        a, b, c, d, e, f, g, h = carry
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        wt = jax.lax.dynamic_index_in_dim(w, t, axis=-1, keepdims=False)
        t1 = h + S1 + ch + k_const[t] + wt
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return (t1 + S0 + maj, a, b, c, d + t1, e, f, g)

    out = jax.lax.fori_loop(
        0, 64, round_, tuple(state[..., i] for i in range(8)))
    return state + jnp.stack(out, axis=-1)


_BYTE_SHIFTS = np.array([24, 16, 8, 0], dtype=np.uint32)


def bytes_to_words(data):
    """uint8[..., 4k] big-endian -> uint32[..., k]."""
    shaped = data.astype(jnp.uint32).reshape(data.shape[:-1] + (-1, 4))
    return jnp.sum(shaped << jnp.asarray(_BYTE_SHIFTS), axis=-1, dtype=jnp.uint32)


def words_to_bytes(words):
    """uint32[..., k] -> uint8[..., 4k] big-endian."""
    b = (words[..., None] >> jnp.asarray(_BYTE_SHIFTS)) & jnp.uint32(0xFF)
    return b.reshape(words.shape[:-1] + (-1,)).astype(jnp.uint8)


def _pad_np(length: int) -> tuple[int, np.ndarray]:
    """Static SHA-256 padding for a message of `length` bytes: returns
    (total_blocks, uint8[pad_len] suffix)."""
    rem = (length + 9) % 64
    pad_len = 9 + (64 - rem if rem else 0)
    suffix = np.zeros(pad_len, dtype=np.uint8)
    suffix[0] = 0x80
    bitlen = length * 8
    suffix[-8:] = np.frombuffer(bitlen.to_bytes(8, "big"), dtype=np.uint8)
    return (length + pad_len) // 64, suffix


def hash_fixed(data):
    """SHA-256 of uint8[..., L] for static L -> uint8[..., 32].

    Padding is appended as a compile-time constant; the (L+pad)/64
    compressions unroll at trace time (L is small for Merkle nodes, and
    static-bounded for block parts)."""
    L = data.shape[-1]
    nblocks, suffix = _pad_np(L)
    sfx = jnp.broadcast_to(jnp.asarray(suffix), data.shape[:-1] + (len(suffix),))
    padded = jnp.concatenate([data, sfx], axis=-1)
    words = bytes_to_words(padded)
    state = jnp.broadcast_to(jnp.asarray(IV), data.shape[:-1] + (8,))
    for i in range(nblocks):
        state = compress(state, words[..., 16 * i : 16 * (i + 1)])
    return words_to_bytes(state)


hash_fixed_jit = jax.jit(hash_fixed)
