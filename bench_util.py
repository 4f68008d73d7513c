"""Scalar Ed25519 helpers of the tests: a signer and a one-at-a-time
verifier on OpenSSL (via `cryptography`), with the pure-python RFC 8032
oracle as the fallback. tests/test_ed25519.py and tests/benchrec/ sign
and cross-check with them; nothing in the package imports this file.
"""

from __future__ import annotations

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
    HAVE_OPENSSL = True
except ImportError:  # pragma: no cover - image always has cryptography
    Ed25519PrivateKey = Ed25519PublicKey = None
    HAVE_OPENSSL = False


def fast_signer(seed: bytes):
    """sign(msg) -> 64-byte signature for the given 32-byte seed;
    OpenSSL when available (ns/sig), bit-identical pure-python oracle
    otherwise."""
    if HAVE_OPENSSL:
        return Ed25519PrivateKey.from_private_bytes(seed).sign
    from tendermint_tpu.utils import ed25519_ref as ref
    return lambda msg: ref.sign(seed, msg)


def scalar_verify_one():
    """verify(pub, msg, sig) -> bool, one at a time, fastest scalar
    backend available."""
    if HAVE_OPENSSL:
        def verify(pub, msg, sig):
            try:
                Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
                return True
            except Exception:
                return False
        return verify
    from tendermint_tpu.utils import ed25519_ref as ref
    return lambda pub, msg, sig: ref.verify(pub, msg, sig)
