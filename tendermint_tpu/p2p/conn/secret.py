"""SecretConnection — authenticated encryption for peer links.

Behavioral parity with p2p/conn/secret_connection.go (STS-like protocol):
ephemeral DH, keys derived from the shared secret, then each side proves
its long-term Ed25519 identity by signing the handshake challenge.

TPU-era redesign of the primitives: X25519 ephemeral DH + HKDF-SHA256 key
derivation + ChaCha20Poly1305 AEAD frames with counter nonces (the
reference uses nacl/secretbox + SHA-256 nonce dance). Frames are
length-prefixed ciphertexts; max plaintext per frame is 1024 bytes to
match the reference's framing (:22).

Handshake transcript:
  1. exchange 32-byte ephemeral X25519 pubkeys (plaintext)
  2. secret = X25519(our_eph, their_eph)
     (k_send, k_recv, challenge) = HKDF(secret, info=sorted eph pubs)
  3. over the now-encrypted link, exchange (node pubkey, sig(challenge))
     and verify — the authenticated remote identity is `remote_pubkey`
"""

from __future__ import annotations

import socket as _socket
import struct
import threading
import time
from typing import List, Optional

# `cryptography` (OpenSSL) is OPTIONAL: its module-top import used to
# kill collection of every test file that transitively imports the p2p
# stack on containers without the package. When absent, the RFC-exact
# pure-python fallback in purecrypto.py serves the same wire protocol
# (X25519 + HKDF-SHA256 + ChaCha20Poly1305), so nodes with and without
# OpenSSL interoperate — the fallback is just slower (~1 ms/KB frame).
try:
    from cryptography.hazmat.primitives.asymmetric.x25519 import (
        X25519PrivateKey,
        X25519PublicKey,
    )
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
    from cryptography.hazmat.primitives.hashes import SHA256
    from cryptography.hazmat.primitives.kdf.hkdf import HKDF
    HAVE_CRYPTOGRAPHY = True
except ImportError:
    HAVE_CRYPTOGRAPHY = False

from tendermint_tpu import native, telemetry
from tendermint_tpu.p2p.conn import burst as burst_cfg
from tendermint_tpu.p2p.conn import purecrypto
from tendermint_tpu.types import encoding
from tendermint_tpu.types.keys import PubKey

DATA_MAX_SIZE = 1024  # plaintext bytes per frame (secret_connection.go:22)
_TAG = 16             # poly1305 tag
_RECV_CHUNK = 65536   # burst-mode socket read size

# Frame-plane crypto timings, observed once per seal/open call (a call
# covers a whole burst, so per-frame cost = _sum / frames). Buckets are
# µs-scaled: a native burst seals ~10µs/frame, purecrypto ~4ms/frame.
_AEAD_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 2.5e-2, 1e-1, 1.0)
_m_seal = telemetry.histogram(
    "p2p_seal_seconds", "AEAD seal wall time per call (burst = 1 call)",
    buckets=_AEAD_BUCKETS)
_m_open = telemetry.histogram(
    "p2p_open_seconds", "AEAD open wall time per call (burst = 1 call)",
    buckets=_AEAD_BUCKETS)
# Frames under the calls above: seal µs/frame = seal_seconds_sum /
# frames_sealed_total.
_m_sealed = telemetry.counter(
    "p2p_frames_sealed_total", "Frames sealed (all paths)")
_m_opened = telemetry.counter(
    "p2p_frames_opened_total", "Frames opened (all paths)")


def _hkdf(secret: bytes, info: bytes, n: int) -> bytes:
    """RFC 5869 HKDF-SHA256."""
    if HAVE_CRYPTOGRAPHY:
        return HKDF(algorithm=SHA256(), length=n, salt=None,
                    info=info).derive(secret)
    return purecrypto.hkdf_sha256(secret, info, n)


def _aead(key: bytes):
    if HAVE_CRYPTOGRAPHY:
        return ChaCha20Poly1305(key)
    return purecrypto.ChaCha20Poly1305(key)


def _eph_keypair():
    """-> (private_handle, public32). The private handle is whatever
    _dh() below expects for the active backend."""
    if HAVE_CRYPTOGRAPHY:
        priv = X25519PrivateKey.generate()
        return priv, priv.public_key().public_bytes_raw()
    return purecrypto.x25519_keypair()


def _dh(priv, their_pub32: bytes) -> bytes:
    if HAVE_CRYPTOGRAPHY:
        return priv.exchange(X25519PublicKey.from_public_bytes(their_pub32))
    return purecrypto.x25519(priv, their_pub32)


class _Cipher:
    """One direction: ChaCha20Poly1305 with a 96-bit counter nonce. The
    raw key is retained so the native burst kernels (which take key
    bytes, not an AEAD object) share the same counter stream — burst and
    per-frame calls may interleave freely on one cipher."""

    def __init__(self, key: bytes):
        self.key = bytes(key)
        self.aead = _aead(key)
        self.nonce = 0

    def _next_nonce(self) -> bytes:
        n = self.nonce
        self.nonce += 1
        return n.to_bytes(12, "little")

    def seal(self, plaintext: bytes) -> bytes:
        return self.aead.encrypt(self._next_nonce(), plaintext, b"")

    def open(self, ciphertext: bytes) -> bytes:
        return self.aead.decrypt(self._next_nonce(), ciphertext, b"")


class SecretConnection:
    """Wraps a raw socket-like conn (sendall/recv/close) with AEAD frames.

    `make(conn, node_key)` performs the full handshake and returns the
    connection with `remote_pubkey` authenticated."""

    def __init__(self, conn, send_cipher: _Cipher, recv_cipher: _Cipher,
                 remote_pubkey: bytes = b""):
        self.conn = conn
        self._send = send_cipher         #: guarded_by _send_lock
        self._recv = recv_cipher         #: guarded_by _rlock
        self.remote_pubkey = remote_pubkey
        self._send_lock = threading.Lock()
        # recv-side lock mirroring the send lock: two concurrent read()
        # callers would otherwise interleave counter nonces (reader A
        # takes nonce n, reader B nonce n+1, but B's frame arrives
        # first) and poison the stream with spurious InvalidTags.
        self._rlock = threading.Lock()
        self._rbuf = bytearray()  #: guarded_by _rlock (socket read-ahead)
        self._burst = burst_cfg.resolve()[0]

    # ------------------------------------------------------------- handshake

    @classmethod
    def make(cls, conn, node_key) -> "SecretConnection":
        eph_priv, eph_pub = _eph_keypair()
        conn.sendall(eph_pub)
        their_eph = _read_exact(conn, 32)

        secret = _dh(eph_priv, their_eph)
        lo, hi = sorted((eph_pub, their_eph))
        keys = _hkdf(secret, b"tendermint_tpu/secret/" + lo + hi, 96)
        k_lo, k_hi, challenge = keys[:32], keys[32:64], keys[64:]
        if eph_pub == lo:
            send_c, recv_c = _Cipher(k_lo), _Cipher(k_hi)
        else:
            send_c, recv_c = _Cipher(k_hi), _Cipher(k_lo)

        sc = cls(conn, send_c, recv_c)

        # authenticate over the encrypted link
        auth = encoding.cdumps({"pubkey": node_key.pubkey.hex(),
                                "sig": node_key.sign(challenge).hex()})
        sc.write(auth)
        their_auth = encoding.cloads(sc.read())
        their_pub = bytes.fromhex(their_auth["pubkey"])
        their_sig = bytes.fromhex(their_auth["sig"])
        if not PubKey(their_pub).verify(challenge, their_sig):
            conn.close()
            raise ValueError("secret handshake: invalid identity signature")
        sc.remote_pubkey = their_pub
        return sc

    # ----------------------------------------------------------------- frames

    def write(self, data: bytes) -> int:
        """Fragment into <=1024B plaintext frames (write in one lock so
        concurrent writers cannot interleave nonce order). With burst on,
        every frame of the payload seals in one native call and ships in
        one sendall — same nonces, same wire bytes as the per-frame
        path."""
        with self._send_lock:
            if self._burst:
                self._seal_and_send_locked(_chunk(data))
                return len(data)
            # pre-burst path, byte- and syscall-identical (escape hatch;
            # the per-frame timing below is telemetry only)
            n = 0
            tele = telemetry.enabled()
            view = memoryview(data)
            while True:
                chunk = bytes(view[:DATA_MAX_SIZE])
                view = view[len(chunk):]
                t0 = time.perf_counter() if tele else 0.0
                sealed = self._send.seal(struct.pack(">H", len(chunk)) + chunk)
                if tele:
                    _m_seal.observe(time.perf_counter() - t0)
                    _m_sealed.inc()
                self.conn.sendall(struct.pack(">I", len(sealed)) + sealed)
                n += len(chunk)
                if len(view) == 0:
                    break
            return n

    def write_many(self, chunks: List[bytes]) -> int:
        """Vectored frame write: each chunk (<=1024B) becomes exactly one
        frame — the layout MConnection needs, where one frame is one
        packet. The whole burst seals in one native call (GIL released)
        and ships in one sendall; wire bytes are identical to calling
        write(chunk) per chunk."""
        total = 0
        for c in chunks:
            if len(c) > DATA_MAX_SIZE:
                raise ValueError(f"frame chunk exceeds {DATA_MAX_SIZE}B")
            total += len(c)
        with self._send_lock:
            if self._burst:
                self._seal_and_send_locked(list(chunks))
            else:
                for chunk in chunks:
                    sealed = self._send.seal(
                        struct.pack(">H", len(chunk)) + chunk)
                    self.conn.sendall(
                        struct.pack(">I", len(sealed)) + sealed)
        return total

    def _seal_wire_locked(self, chunks: List[bytes]) -> bytes:
        """Wire bytes for `chunks`, one frame each — exactly what
        write_many would sendall (caller holds _send_lock)."""
        t0 = time.perf_counter() if telemetry.enabled() else 0.0
        wire = native.aead_seal_burst(self._send.key, self._send.nonce,
                                      chunks)
        if wire is not None:
            self._send.nonce += len(chunks)
        else:
            # no native kernels: per-frame python seal, same bytes
            parts = []
            for chunk in chunks:
                sealed = self._send.seal(
                    struct.pack(">H", len(chunk)) + chunk)
                parts.append(struct.pack(">I", len(sealed)))
                parts.append(sealed)
            wire = b"".join(parts)
        if t0:
            _m_seal.observe(time.perf_counter() - t0)
            _m_sealed.inc(len(chunks))
        return wire

    def _seal_and_send_locked(self, chunks: List[bytes]) -> None:
        self.conn.sendall(self._seal_wire_locked(chunks))

    def seal_frames(self, chunks: List[bytes]) -> bytes:
        """Non-blocking codec surface for the loop reactor: the wire
        bytes for `chunks` (one <=1024B frame each) WITHOUT touching
        the socket — byte-identical to what write_many sends. The loop
        owns the socket; the link owns the cipher stream."""
        for c in chunks:
            if len(c) > DATA_MAX_SIZE:
                raise ValueError(f"frame chunk exceeds {DATA_MAX_SIZE}B")
        with self._send_lock:
            return self._seal_wire_locked(list(chunks))

    def read(self) -> bytes:
        """One frame's plaintext (<=1024B). b'' on clean EOF."""
        with self._rlock:
            if not self._burst:
                return self._read_frame_unbuffered_locked()
            frames = self._read_frames_locked(limit=1)
            return frames[0] if frames else b""

    def read_burst(self) -> List[bytes]:
        """Every complete frame already buffered from the socket, opened
        in one native call — blocks only for the first. [] on clean EOF.
        Interoperates frame-for-frame with a per-frame peer: burst is a
        receive-side batching decision, not a wire format."""
        with self._rlock:
            if not self._burst:
                frame = self._read_frame_unbuffered_locked()
                return [frame] if frame != b"" else []
            return self._read_frames_locked(limit=0)

    def _read_frame_unbuffered_locked(self) -> bytes:
        """The pre-burst read path (escape hatch): exact-size recvs,
        one python AEAD open per frame. Caller holds _rlock."""
        hdr = _read_exact(self.conn, 4, allow_eof=True)
        if hdr == b"":
            return b""
        (clen,) = struct.unpack(">I", hdr)
        if clen > DATA_MAX_SIZE + 2 + _TAG:
            raise ValueError(f"oversized secret frame: {clen}")
        sealed = _read_exact(self.conn, clen)
        t0 = time.perf_counter() if telemetry.enabled() else 0.0
        plain = self._recv.open(sealed)
        if t0:
            _m_open.observe(time.perf_counter() - t0)
            _m_opened.inc()
        return _strip_frame(plain)

    def _fill_locked(self, need: int, allow_eof: bool = False) -> bool:
        """Grow the read-ahead buffer to >= need bytes. False on clean
        EOF (only when allow_eof and nothing is buffered)."""
        while len(self._rbuf) < need:
            chunk = self.conn.recv(_RECV_CHUNK)
            if not chunk:
                if allow_eof and not self._rbuf:
                    return False
                raise ConnectionError("unexpected EOF")
            self._rbuf += chunk
        return True

    def _read_frames_locked(self, limit: int = 0) -> List[bytes]:
        """Parse sealed frames out of the read-ahead buffer (blocking
        until the first is complete), open them in one burst, and return
        the payloads. limit=0 means every complete frame buffered."""
        if not self._fill_locked(4, allow_eof=True):
            return []
        sealed: List[bytes] = []
        while len(self._rbuf) >= 4:
            (clen,) = struct.unpack(">I", bytes(self._rbuf[:4]))
            if clen > DATA_MAX_SIZE + 2 + _TAG:
                raise ValueError(f"oversized secret frame: {clen}")
            if len(self._rbuf) < 4 + clen:
                if sealed:
                    break  # later frames: don't block mid-burst
                self._fill_locked(4 + clen)
            sealed.append(bytes(self._rbuf[4:4 + clen]))
            del self._rbuf[:4 + clen]
            if limit and len(sealed) >= limit:
                break
        return self._open_sealed_locked(sealed)

    def _open_sealed_locked(self, sealed: List[bytes]) -> List[bytes]:
        if not sealed:
            return []
        t0 = time.perf_counter() if telemetry.enabled() else 0.0
        plains = None
        if len(sealed) > 1:
            plains = native.aead_open_burst(self._recv.key,
                                            self._recv.nonce, sealed)
            if plains is not None:
                self._recv.nonce += len(sealed)
        if plains is None:
            plains = [self._recv.open(f) for f in sealed]
        if t0:
            _m_open.observe(time.perf_counter() - t0)
            _m_opened.inc(len(sealed))
        return [_strip_frame(p) for p in plains]

    def feed_wire(self, data: bytes) -> List[bytes]:
        """Non-blocking codec surface for the loop reactor: append raw
        socket bytes to the read-ahead buffer and return every COMPLETE
        frame's plaintext (one burst open). Never reads the socket;
        partial frames stay buffered until the next feed. feed_wire(b'')
        drains frames the handshake's over-read already buffered."""
        with self._rlock:
            if data:
                self._rbuf += data
            sealed: List[bytes] = []
            while len(self._rbuf) >= 4:
                (clen,) = struct.unpack(">I", bytes(self._rbuf[:4]))
                if clen > DATA_MAX_SIZE + 2 + _TAG:
                    raise ValueError(f"oversized secret frame: {clen}")
                if len(self._rbuf) < 4 + clen:
                    break
                sealed.append(bytes(self._rbuf[4:4 + clen]))
                del self._rbuf[:4 + clen]
            return self._open_sealed_locked(sealed)

    def close(self) -> None:
        # shutdown wakes any recv() blocked in another thread and sends
        # FIN immediately; bare close() does neither reliably
        try:
            self.conn.shutdown(_socket.SHUT_RDWR)
        except (OSError, AttributeError):
            pass
        try:
            self.conn.close()
        except OSError:
            pass


def _chunk(data: bytes) -> List[bytes]:
    """<=1024B plaintext chunks; an empty payload is one empty frame
    (the pre-burst write loop sealed exactly that)."""
    if not data:
        return [b""]
    view = memoryview(data)
    return [bytes(view[i:i + DATA_MAX_SIZE])
            for i in range(0, len(data), DATA_MAX_SIZE)]


def _strip_frame(plain: bytes) -> bytes:
    (dlen,) = struct.unpack(">H", plain[:2])
    if 2 + dlen > len(plain):
        raise ValueError(
            f"secret frame length {dlen} exceeds plaintext "
            f"({len(plain) - 2} data bytes)")
    return plain[2:2 + dlen]


def _read_exact(conn, n: int, allow_eof: bool = False) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            if allow_eof and not buf:
                return b""
            raise ConnectionError("unexpected EOF")
        buf += chunk
    return buf
