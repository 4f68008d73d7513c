"""Shared scalar-crypto shims for the bench suite (bench.py,
bench_fastsync.py, bench_lite.py).

Baselines model the reference's execution: one scalar Ed25519 op per
signature on a single core (types/validator_set.go:257). OpenSSL (via
`cryptography`) is used when available — it is FASTER than Go's
x/crypto ed25519, so every vs_baseline number is conservative; the
pure-python RFC 8032 oracle is the fallback.
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


class ScalarVerifier:
    """BatchVerifier-shaped adapter that verifies one-at-a-time on the
    scalar backend — the reference's execution model, used as the
    baseline arm of the fast-sync and lite benches."""

    def __init__(self):
        self.stats = {"calls": 0, "sigs": 0, "jax_sigs": 0}
        self._verify = scalar_verify_one()

    def verify(self, items):
        import numpy as np
        self.stats["calls"] += 1
        self.stats["sigs"] += len(items)
        return np.array([self._verify(p, m, s) for p, m, s in items],
                        np.bool_)

    def verify_one(self, pub, msg, sig) -> bool:
        return self._verify(pub, msg, sig)

    def verify_async(self, items):
        """Scalar work has no async dimension: verify now, hand back the
        result thunk (keeps the reactor's pipelined loop verifier-shape
        agnostic)."""
        out = self.verify(items)
        return lambda: out


def free_port_block(k: int) -> int:
    """A base port with k consecutively-bindable ports (multi-node
    harnesses need two per node; one busy port in the range reads as a
    consensus failure). Shared by the socket bench and the e2e tests.

    Ports come from BELOW the kernel's ephemeral range (32768-60999 on
    this host): the probe-then-bind window is seconds long, and an
    outgoing connection's auto-assigned source port can steal a probed
    ephemeral-range port in between — the flaky 'Address already in
    use' node-boot failure."""
    import random
    import socket
    for _ in range(50):
        base = random.randrange(20000, 32000, 2) | 1
        socks = []
        try:
            for off in range(k):
                s = socket.socket()
                s.bind(("127.0.0.1", base + off))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block found")


def node_child_env(repo: str) -> dict:
    """Environment for spawned node processes: pinned to the CPU
    backend (a chip belongs to one process, and a parent that holds it
    can start CPU children — checked on the chip host, see the verify
    skill), without the compilation cache a CPU backend gets none of
    (utils/compile_cache)."""
    import os
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env
