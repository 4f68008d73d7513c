"""Key types: Ed25519 (consensus-default, TPU-batched verification) and
Secp256k1 (go-crypto's second key type — lite/performance_test.go:10-105
exercises both). Address = first 20 bytes of SHA-256(pubkey) (the
reference derives addresses via RIPEMD160, p2p/key.go:43-47; SHA-256 is
this rebuild's single hash primitive).

Secp256k1 is OFF the hot path (host-side ECDSA via OpenSSL); the batch
verifier routes mixed valsets by pubkey length — 32 bytes = ed25519 to
the device, 33 bytes = compressed SEC1 secp256k1 on host."""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass

from tendermint_tpu.utils import ed25519_ref as _ref
from tendermint_tpu.utils import knobs


def address_of(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


def _openssl_key_class():
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
        return Ed25519PrivateKey
    except ImportError:
        return None


_ossl_pub_cls = None

_P255 = (1 << 255) - 19


def _noncanonical_point(enc: bytes) -> bool:
    """Point encodings where OpenSSL (ref10) is LENIENT but this
    build's oracle/kernels reject: y >= p, or the x=0 identity row
    (y = ±1) carrying a set sign bit (RFC 8032 §5.1.3). Routed to the
    pure oracle so verdicts are bit-identical everywhere — a scalar/
    batch or per-node verdict split on adversarial encodings would be
    a consensus fork."""
    y = int.from_bytes(enc, "little") & ((1 << 255) - 1)
    if y >= _P255:
        return True
    sign = enc[31] >> 7
    return bool(sign) and y in (1, _P255 - 1)


def _openssl_verify(pubkey: bytes, msg: bytes, sig: bytes):
    """Scalar Ed25519 verify via OpenSSL (~130us vs ~5ms for the pure
    oracle — the reference's scalar path is fast Go crypto, so the
    interactive single-vote path here must not cost milliseconds).
    Returns None when `cryptography` is unavailable or the inputs fall
    in OpenSSL's leniency gap (callers fall back to the pure oracle);
    verdicts are differential-tested against the oracle including the
    adversarial encodings."""
    global _ossl_pub_cls
    if _ossl_pub_cls is None:
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PublicKey,
            )
            _ossl_pub_cls = Ed25519PublicKey
        except ImportError:
            _ossl_pub_cls = False
    if _ossl_pub_cls is False:
        return None
    if len(pubkey) == 32 and len(sig) == 64 and (
            _noncanonical_point(pubkey) or _noncanonical_point(sig[:32])):
        return None  # leniency gap: the pure oracle decides
    try:
        _ossl_pub_cls.from_public_bytes(pubkey).verify(sig, msg)
        return True
    except Exception:
        return False


@dataclass(frozen=True)
class PubKey:
    ed25519: bytes  # 32 bytes

    @property
    def address(self) -> bytes:
        return address_of(self.ed25519)

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """Scalar verify — interactive paths only. Hot paths use
        models/verifier.BatchVerifier."""
        return verify_any(self.ed25519, msg, sig)

    def to_obj(self):
        return {"type": "ed25519", "value": self.ed25519.hex()}

    @classmethod
    def from_obj(cls, obj) -> "PubKey":
        assert obj["type"] == "ed25519"
        return cls(bytes.fromhex(obj["value"]))


@dataclass(frozen=True)
class PrivKey:
    seed: bytes  # 32 bytes

    @classmethod
    def generate(cls, seed: bytes | None = None) -> "PrivKey":
        return cls(seed if seed is not None else os.urandom(32))

    @property
    def pubkey(self) -> PubKey:
        # cached per INSTANCE (not a module-level memo: a global cache
        # would retain raw seeds for the process lifetime, well past the
        # owning key's). The derivation is a ~ms pure-Python point
        # multiply and this property sits on signing/test hot paths.
        pk = self.__dict__.get("_pub")
        if pk is None:
            pk = PubKey(_ref.public_key(self.seed))
            self.__dict__["_pub"] = pk
        return pk

    def sign(self, msg: bytes) -> bytes:
        # OpenSSL signs in ~30us, bit-identical output (Ed25519 signing
        # is deterministic); the handle is cached per instance, same
        # rationale as pubkey. Without OpenSSL the table oracle signs in
        # ~4ms vs ~50ms for the two fresh ladders of ed25519_ref.sign —
        # per-vote signing latency sits on the consensus critical path,
        # so the secret expansion is cached per instance too (the
        # expansion itself is one ladder; utils/ed25519_fast holds no
        # secret state).
        k = self.__dict__.get("_osslk")
        if k is None:
            cls = _openssl_key_class()
            if cls is None:
                exp = self.__dict__.get("_exp")
                if exp is None:
                    a, prefix = _ref.secret_expand(self.seed)
                    exp = (a, prefix, self.pubkey.ed25519)
                    self.__dict__["_exp"] = exp
                from tendermint_tpu.utils import ed25519_fast
                return ed25519_fast.sign_expanded(*exp, msg)
            k = cls.from_private_bytes(self.seed)
            self.__dict__["_osslk"] = k
        return k.sign(msg)

    def to_obj(self):
        return {"type": "ed25519", "value": self.seed.hex()}

    @classmethod
    def from_obj(cls, obj) -> "PrivKey":
        assert obj["type"] == "ed25519"
        return cls(bytes.fromhex(obj["value"]))


# ---------------------------------------------------------------- secp256k1

def _ec():
    """OpenSSL EC bindings, or None when `cryptography` is absent (the
    pure-python utils/secp256k1_ref fallback serves the same DER/SEC1
    wire format)."""
    try:
        from cryptography.hazmat.primitives.asymmetric import ec
        return ec
    except ImportError:
        return None


@dataclass(frozen=True)
class Secp256k1PubKey:
    """Compressed SEC1 point (33 bytes). Signatures are DER-encoded
    ECDSA-SHA256 (opaque bytes, like go-crypto's SignatureSecp256k1)."""
    secp256k1: bytes

    @property
    def address(self) -> bytes:
        return address_of(self.secp256k1)

    def verify(self, msg: bytes, sig: bytes) -> bool:
        ec = _ec()
        if ec is None:
            from tendermint_tpu.utils import secp256k1_ref
            return secp256k1_ref.verify(self.secp256k1, msg, sig)
        try:
            from cryptography.hazmat.primitives import hashes
            pub = ec.EllipticCurvePublicKey.from_encoded_point(
                ec.SECP256K1(), self.secp256k1)
            pub.verify(sig, msg, ec.ECDSA(hashes.SHA256()))
            return True
        except Exception:
            return False

    def to_obj(self):
        return {"type": "secp256k1", "value": self.secp256k1.hex()}

    @classmethod
    def from_obj(cls, obj) -> "Secp256k1PubKey":
        assert obj["type"] == "secp256k1"
        return cls(bytes.fromhex(obj["value"]))


@dataclass(frozen=True)
class Secp256k1PrivKey:
    seed: bytes  # 32-byte big-endian private scalar

    @classmethod
    def generate(cls, seed: bytes | None = None) -> "Secp256k1PrivKey":
        if seed is None:
            seed = os.urandom(32)
        # clamp into [1, n-1] so any 32-byte seed is a valid key
        n = int("fffffffffffffffffffffffffffffffebaaedce6af48a03b"
                "bfd25e8cd0364141", 16)  # secp256k1 group order
        v = (int.from_bytes(seed, "big") % (n - 1)) + 1
        return cls(v.to_bytes(32, "big"))

    def _key(self):
        k = self.__dict__.get("_osslk")
        if k is None:
            ec = _ec()
            k = ec.derive_private_key(int.from_bytes(self.seed, "big"),
                                      ec.SECP256K1())
            self.__dict__["_osslk"] = k
        return k

    @property
    def pubkey(self) -> Secp256k1PubKey:
        pk = self.__dict__.get("_pub")
        if pk is None:
            if _ec() is None:
                from tendermint_tpu.utils import secp256k1_ref
                pk = Secp256k1PubKey(secp256k1_ref.pubkey_of(self.seed))
            else:
                from cryptography.hazmat.primitives import serialization
                pk = Secp256k1PubKey(
                    self._key().public_key().public_bytes(
                        serialization.Encoding.X962,
                        serialization.PublicFormat.CompressedPoint))
            self.__dict__["_pub"] = pk
        return pk

    def sign(self, msg: bytes) -> bytes:
        ec = _ec()
        if ec is None:
            from tendermint_tpu.utils import secp256k1_ref
            return secp256k1_ref.sign(self.seed, msg)
        from cryptography.hazmat.primitives import hashes
        return self._key().sign(msg, ec.ECDSA(hashes.SHA256()))

    def to_obj(self):
        return {"type": "secp256k1", "value": self.seed.hex()}

    @classmethod
    def from_obj(cls, obj) -> "Secp256k1PrivKey":
        assert obj["type"] == "secp256k1"
        return cls(bytes.fromhex(obj["value"]))


def pubkey_from_obj(obj):
    """Type-dispatching factory (the go-crypto PubKey interface wire
    format: {type, value})."""
    if obj["type"] == "ed25519":
        return PubKey.from_obj(obj)
    if obj["type"] == "secp256k1":
        return Secp256k1PubKey.from_obj(obj)
    raise ValueError(f"unknown pubkey type {obj['type']!r}")


def privkey_from_obj(obj):
    if obj["type"] == "ed25519":
        return PrivKey.from_obj(obj)
    if obj["type"] == "secp256k1":
        return Secp256k1PrivKey.from_obj(obj)
    raise ValueError(f"unknown privkey type {obj['type']!r}")


def verify_any(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    """Scalar verify routed by key encoding: 32B = ed25519 (OpenSSL,
    pure-oracle fallback), 33B (02/03 prefix) = compressed secp256k1."""
    if len(pubkey) == 32:
        out = _openssl_verify(pubkey, msg, sig)
        if out is not None:
            return out
        # table upgrade for RESIDENT keys only: steady-state consensus
        # verifies the same validator keys vote after vote (tables get
        # built by the first >= _HOST_TABLE_MIN batch, verify_many
        # below), so the scalar per-vote path runs at table speed
        # (~5ms) instead of two fresh ladders (~25ms) — without letting
        # one-off interactive verifies populate the LRU
        from tendermint_tpu.utils import ed25519_fast
        if ed25519_fast.has_table(pubkey):
            return ed25519_fast.verify(pubkey, msg, sig)
        return _ref.verify(pubkey, msg, sig)
    if len(pubkey) == 33 and pubkey[0] in (2, 3):
        return Secp256k1PubKey(pubkey).verify(msg, sig)
    return False


def _openssl_available() -> bool:
    global _ossl_pub_cls
    if _ossl_pub_cls is None:
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PublicKey,
            )
            _ossl_pub_cls = Ed25519PublicKey
        except ImportError:
            _ossl_pub_cls = False
    return _ossl_pub_cls is not False


# Minimum ed25519 members before a host batch switches to the
# precomputed-table oracle. Gated on batch size for the same reason the
# device predecomp cache is (ops/ed25519._PREDECOMP_MIN_BATCH): tables
# cost a ladder's worth of build per key plus ~60KB residency, which
# only aggregated consensus traffic (stable valsets, votes added in
# batches) amortizes — a one-off interactive verify must not populate
# a cache it will never reuse.
_HOST_TABLE_MIN = knobs.knob_int("TM_TPU_HOST_TABLE_MIN", default=4)


def verify_many(items) -> list:
    """Host-side batch verify: verdicts for (pubkey, msg, sig) triples,
    aligned with `items`. Routing per item matches verify_any exactly,
    with one bulk-only upgrade: when OpenSSL is unavailable (the pure
    oracle would run) and the batch carries >= _HOST_TABLE_MIN ed25519
    members, those route through utils/ed25519_fast — the per-pubkey
    precomputed-table oracle with bit-identical verdicts at ~4-6x the
    throughput. This is the path a small commit or an aggregated vote
    batch takes on hosts without OpenSSL (BatchVerifier's host path)."""
    ed = sum(1 for it in items
             if isinstance(it[0], (bytes, bytearray)) and len(it[0]) == 32)
    if ed >= _HOST_TABLE_MIN and not _openssl_available():
        from tendermint_tpu.utils import ed25519_fast
        return [ed25519_fast.verify(p, m, s)
                if isinstance(p, (bytes, bytearray)) and len(p) == 32
                else verify_any(p, m, s)
                for p, m, s in items]
    return [verify_any(p, m, s) for p, m, s in items]
