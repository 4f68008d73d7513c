"""Plain references, independent of the program: nothing here imports
`tendermint_tpu`.

PlainKV is the KVStore application's semantics written out with
hashlib and a dict: `key=value` transactions applied in order, and the
application hash after each block (256 buckets by crc32 of the key;
a bucket commits to the sum mod 2**256 of its pairs' digests and to
its key count; the hash is the Merkle root over the bucket digests).
The benchmark replays the committed transactions through it and holds
the program's app hashes and read-backs to what it gives.

openssl_verify is the scalar Ed25519 oracle (OpenSSL through
`cryptography`), for lane-by-lane comparison with the device's
verdicts."""

from __future__ import annotations

import hashlib
import struct
import zlib
from typing import Dict, Iterable, List

N_BUCKETS = 256
_EMPTY_BUCKET = hashlib.sha256(b"\x00").digest()
_MOD = 1 << 256


def merkle_root_of_digests(digests: List[bytes]) -> bytes:
    """Root over 32-byte digests: padded to a power of two with zero
    digests, inner node sha256(0x01|l|r), sealed with the leaf count."""
    sha = hashlib.sha256
    n = len(digests)
    m = 1
    while m < n:
        m *= 2
    level = list(digests) + [b"\x00" * 32] * (m - n)
    while len(level) > 1:
        level = [sha(b"\x01" + level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return sha(b"\x02" + struct.pack("<Q", n) + level[0]).digest()


class PlainKV:
    def __init__(self):
        self.store: Dict[bytes, bytes] = {}
        self._pair: Dict[bytes, int] = {}     # key -> its pair digest
        self._acc = [0] * N_BUCKETS
        self._cnt = [0] * N_BUCKETS
        self._digest = [_EMPTY_BUCKET] * N_BUCKETS

    def apply_block(self, txs: Iterable[bytes]) -> bytes:
        """Apply one block's transactions in order; the app hash after
        it (what the NEXT block's header carries)."""
        sha = hashlib.sha256
        crc = zlib.crc32
        store, pair, acc, cnt = self.store, self._pair, self._acc, self._cnt
        dirty = set()
        for tx in txs:
            k, sep, v = tx.partition(b"=")
            if not sep:
                k = v = tx
            store[k] = v
            dirty.add(k)
        touched = set()
        for k in dirty:
            v = store[k]
            b = crc(k) & (N_BUCKETS - 1)
            touched.add(b)
            d = int.from_bytes(sha(len(k).to_bytes(4, "little") + k +
                                   len(v).to_bytes(4, "little") + v
                                   ).digest(), "little")
            old = pair.get(k)
            if old is None:
                cnt[b] += 1
            else:
                acc[b] -= old
            acc[b] += d
            pair[k] = d
        for b in touched:
            self._digest[b] = sha(
                b"\x00" + (acc[b] % _MOD).to_bytes(32, "little") +
                cnt[b].to_bytes(8, "little")).digest()
        if not store:
            return b"\x00" * 32
        return merkle_root_of_digests(self._digest)


def openssl_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def openssl_signer(seed: bytes):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    return Ed25519PrivateKey.from_private_bytes(seed)
