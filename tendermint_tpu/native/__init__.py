"""Native host-ops loader.

Four extensions are compiled from the C++ sources in this directory on
first use (g++ is in the image; a build takes seconds): the ctypes
library `_hostops` and the CPython modules `_tmcodec`, `_tmprep` and
`_tmkv`. A built file is named after the hash of the sources and flags
it was built from, so a binary left over from other sources, or copied
in from another tree, is never loaded: the name it would need does not
exist until this tree builds it.

Every entry point has a pure-Python fallback, taken where the host has
no C++ toolchain or TM_TPU_NO_NATIVE is set. A build or load that FAILS
on a host that has the toolchain is an error (NativeBuildError), raised
on every use, never a quiet return to the fallbacks. `status()` reports
what is loaded.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

from tendermint_tpu.utils import knobs
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    """A native extension failed to build or load although the host has
    a compiler."""


class _Ext:
    """One extension: its sources, how to build it, and the loaded
    handle (a ctypes CDLL, or the imported CPython module)."""

    def __init__(self, name: str, src: str, deps: tuple = (),
                 opt: str = "-O2", std: str = "c++17",
                 cpython: bool = True, bind=None, extra: tuple = ()):
        # deps: sources the src #includes. std: per extension — only
        # kvcore needs c++20 (transparent unordered_map lookup). bind:
        # declares a ctypes library's signatures once it is open.
        # extra: further flags (prep's -pthread).
        self.name = name
        self.src = src
        self.deps = deps
        self.opt = opt
        self.std = std
        self.cpython = cpython
        self.bind = bind
        self.extra = extra
        self.handle = None
        self.path: Optional[str] = None
        self._tried = False
        self._error: Optional[BaseException] = None

    def _flags(self) -> list:
        return [self.opt, "-shared", "-fPIC", f"-std={self.std}",
                *self.extra]

    def lib_path(self) -> str:
        """<dir>/<name>.<hash of flags + source bytes>.so"""
        h = hashlib.sha256(" ".join(self._flags()).encode())
        for path in (self.src,) + self.deps:
            with open(path, "rb") as f:
                h.update(f.read())
        return os.path.join(os.path.dirname(self.src),
                            f"{self.name}.{h.hexdigest()[:16]}.so")

    def build_lib(self) -> Optional[str]:
        """Path of the library built from the sources as they are now,
        compiling it unless that exact file exists. None where the host
        has no toolchain. Builds of other sources are removed."""
        lib = self.lib_path()
        if os.path.exists(lib):
            return lib
        cmd = ["g++"] + self._flags()
        if self.cpython:
            import sysconfig
            inc = sysconfig.get_paths().get("include")
            if not inc or not os.path.exists(os.path.join(inc, "Python.h")):
                return None
            cmd.append(f"-I{inc}")
        # per-PID tmp: concurrent builders must not interleave writes
        # into one tmp file (os.replace keeps the install atomic)
        tmp = f"{lib[:-3]}.{os.getpid()}.so.tmp"
        try:
            subprocess.run(cmd + [self.src, "-o", tmp], check=True,
                           capture_output=True, timeout=300)
        except FileNotFoundError:
            return None  # no g++ on this host
        except subprocess.CalledProcessError as e:
            raise NativeBuildError(
                f"{self.name}: g++ failed on {self.src}:\n"
                f"{e.stderr.decode(errors='replace')[-2000:]}") from e
        except subprocess.TimeoutExpired as e:
            raise NativeBuildError(
                f"{self.name}: g++ timed out on {self.src}") from e
        os.replace(tmp, lib)
        pattern = os.path.join(os.path.dirname(lib), self.name + ".*so")
        for old in glob.glob(pattern):
            if old != lib:
                try:
                    os.unlink(old)
                except OSError:
                    pass  # another process already removed it
        return lib

    def ensure_loaded(self):
        """The loaded handle, building first if needed; None only where
        native code is switched off or the host cannot build."""
        if not self._tried:
            with _lock:
                if not self._tried:
                    if not knobs.knob_set("TM_TPU_NO_NATIVE"):
                        try:
                            self.path = self.build_lib()
                            if self.path is not None:
                                self.handle = self._open_lib(self.path)
                        except (NativeBuildError, OSError,
                                ImportError) as e:
                            self._error = e
                    self._tried = True  # last: unlocked readers key on it
        if self._error is not None:
            raise NativeBuildError(
                f"native extension {self.name} is unusable: "
                f"{self._error}") from self._error
        return self.handle

    def _open_lib(self, path: str):
        if not self.cpython:
            lib = ctypes.CDLL(path)
            if self.bind is not None:
                self.bind(lib)
            return lib
        import importlib.util
        spec = importlib.util.spec_from_file_location(self.name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _src(name: str) -> str:
    return os.path.join(_HERE, name)


def _bind_hostops(lib) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.tm_sha256_batch.argtypes = [u8p, u64p, ctypes.c_uint64, u8p]
    lib.tm_merkle_root.argtypes = [u8p, u64p, ctypes.c_uint64, u8p]
    lib.tm_merkle_root_from_digests.argtypes = [
        u8p, ctypes.c_uint64, u8p]
    lib.tm_merkle_proof.argtypes = [u8p, u64p, ctypes.c_uint64,
                                    ctypes.c_uint64, u8p, u8p]
    lib.tm_merkle_proof.restype = ctypes.c_uint64
    lib.tm_merkle_tree_proofs.argtypes = [u8p, u64p, ctypes.c_uint64,
                                          u8p, u8p]
    lib.tm_merkle_tree_proofs.restype = ctypes.c_uint64
    lib.tm_partset_build.argtypes = [u8p, ctypes.c_uint64,
                                     ctypes.c_uint64, u8p, u8p]
    lib.tm_partset_build.restype = ctypes.c_uint64
    lib.tm_ed25519_prepare.argtypes = [u8p, u8p, u8p, u64p,
                                       ctypes.c_uint64, u8p, u8p]
    lib.tm_aead_seal_one.argtypes = [
        u8p, u8p, u8p, ctypes.c_uint64, u8p, ctypes.c_uint64, u8p]
    lib.tm_aead_seal_burst.argtypes = [
        u8p, ctypes.c_uint64, ctypes.c_uint32, u8p, u64p,
        ctypes.c_uint64, u8p]
    lib.tm_aead_open_burst.argtypes = [
        u8p, ctypes.c_uint64, ctypes.c_uint32, u8p, u64p,
        ctypes.c_uint64, u8p]
    lib.tm_aead_open_burst.restype = ctypes.c_int64


_HOSTOPS = _Ext("_hostops", _src("hostops.cpp"), opt="-O3", cpython=False,
                bind=_bind_hostops)
# A true CPython extension (not ctypes): the canonical-JSON encoder
# walks Python object graphs, and the decoder of a block's transaction
# list builds them, which a C ABI can't.
_CODEC = _Ext("_tmcodec", _src("codec.cpp"))
# Batched Ed25519 verify-prep + signing phases: takes the verifier's
# items list and returns the device-bound arrays in one call (GIL
# released for the SHA-512 loop, which may run on several std::threads).
# prep.cpp #includes hostops.cpp.
_PREP = _Ext("_tmprep", _src("prep.cpp"), deps=(_src("hostops.cpp"),),
             opt="-O3", extra=("-pthread",))
# Native KVStore core.
_KV = _Ext("_tmkv", _src("kvcore.cpp"), deps=(_src("hostops.cpp"),),
           opt="-O3", std="c++20")
_EXTS = (_HOSTOPS, _CODEC, _PREP, _KV)


def status() -> dict:
    """Per extension, whether it is loaded and from which file (the
    file name carries the hash of the sources it was built from).
    Builds and loads whatever is not loaded yet; a failure raises."""
    return {e.name: {"loaded": e.ensure_loaded() is not None,
                     "file": e.path and os.path.basename(e.path)}
            for e in _EXTS}


def _load():
    return _HOSTOPS.ensure_loaded()


def available() -> bool:
    return _load() is not None


def codec():
    """The _tmcodec extension module, or None when unavailable.
    Exposes canonical_dumps(obj)->bytes, split_hex_array(data, path)
    -> (items, rest) and the Fallback exception of both."""
    return _CODEC.ensure_loaded()


def _prep():
    return _PREP.ensure_loaded()


def kv():
    """The _tmkv extension module (native KVStore core), or None."""
    return _KV.ensure_loaded()


def _prep_arrays(out, n: int):
    """The extension's five byte buffers as numpy views."""
    if out is None:
        return None
    import numpy as np
    pk_b, rb_b, s_b, h_b, pre_b = out
    as_mat = lambda b: np.frombuffer(b, np.uint8).reshape(n, 32)
    pre = np.frombuffer(pre_b, np.uint8).astype(bool)
    return as_mat(pk_b), as_mat(rb_b), as_mat(s_b), as_mat(h_b), pre


def prep_items(items, threads: int = 1):
    """One-call verify prep: items [(pk, msg, sig), ...] ->
    (pk u8[N,32], R u8[N,32], s u8[N,32], h u8[N,32], pre bool[N])
    numpy views, or None when unavailable / when the batch needs the
    general path (secp256k1 keys, non-bytes members). With `threads`
    > 1 the SHA-512 loop runs over that many contiguous shards of the
    batch at once, on threads that end before the call returns; the
    arrays are the same, bit for bit."""
    mod = _prep()
    if mod is None:
        return None
    return _prep_arrays(mod.prep_items(items, threads), len(items))


def prep_columns(pk, sigs, msgs, idx, threads: int = 1):
    """prep_items for a batch held as columns (types/sigcolumns.py):
    lane i is (pk[i], msgs[idx[i]], sigs[i]). The same five arrays, bit
    for bit, or None when unavailable / when a member is not bytes."""
    mod = _prep()
    if mod is None:
        return None
    import numpy as np
    return _prep_arrays(
        mod.prep_columns(np.ascontiguousarray(pk, np.uint8), sigs, msgs,
                         np.ascontiguousarray(idx, np.int32), threads),
        len(sigs))


def walk_votes(pcs, height: int, round_: int, precommit: int, template):
    """The vote walk of ValidatorSet.commit_verification_items in one
    call: `pcs` a commit's precommits, `template(block_id)` ->
    (prefix str, suffix str, for_block) asked once per distinct block
    id. -> what types/validator_set._walk_votes returns (sigs,
    msgs, idx int32[n], for_block bool[n], absent, all_for; the two
    arrays read-only), raising its ValueErrors, or None when unavailable
    / for a commit that is not read as expected (pcs not a list, a field
    that is no `int` within int64, a signature that is no `bytes`): the
    Python loop then judges it."""
    mod = _prep()
    if mod is None:
        return None
    out = mod.walk_votes(pcs, height, round_, precommit, template)
    if out is None:
        return None
    import numpy as np
    sigs, msgs, idx, for_block, absent, all_for = out
    return (sigs, msgs, np.frombuffer(idx, np.int32),
            np.frombuffer(for_block, np.bool_), absent, all_for)


def _pack(items: List[bytes]):
    data = b"".join(items)
    n = len(items)
    if n < 512:
        # plain-Python offsets beat the numpy round-trip for the small
        # per-block calls (merkle trees of ~10-100 leaves) that dominate
        # the sync loop
        off = [0] * (n + 1)
        t = 0
        for i, it in enumerate(items):
            t += len(it)
            off[i + 1] = t
        offsets = (ctypes.c_uint64 * (n + 1))(*off)
    else:
        import numpy as np
        off = np.zeros(n + 1, np.uint64)
        np.cumsum(np.fromiter((len(it) for it in items), np.uint64, n),
                  out=off[1:])
        offsets = (ctypes.c_uint64 * (n + 1)).from_buffer_copy(
            off.tobytes())
    buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(
        data or b"\x00")
    return buf, offsets


def sha256_batch(items: List[bytes]) -> Optional[List[bytes]]:
    lib = _load()
    if lib is None:
        return None
    buf, offsets = _pack(items)
    out = (ctypes.c_uint8 * (32 * len(items)))()
    lib.tm_sha256_batch(buf, offsets, len(items), out)
    raw = bytes(out)
    return [raw[32 * i:32 * (i + 1)] for i in range(len(items))]


def merkle_root(items: List[bytes]) -> Optional[bytes]:
    # large trees: the CPython-API path (no ctypes offset packing) —
    # the wrapper overhead exceeds the hashing at ~5,000 leaves
    if len(items) >= 256:
        mod = _prep()
        if mod is not None:
            try:
                return mod.merkle_root_items(items)
            except TypeError:
                pass  # non-bytes items: fall through to the packer
    lib = _load()
    if lib is None:
        return None
    buf, offsets = _pack(items)
    out = (ctypes.c_uint8 * 32)()
    lib.tm_merkle_root(buf, offsets, len(items), out)
    return bytes(out)


def merkle_root_from_digests(digests) -> Optional[bytes]:
    """digests: list of 32-byte hashes, OR a bytes-like blob of
    concatenated digests (len % 32 == 0) — the blob path avoids a
    join+copy for callers that maintain a flat digest buffer."""
    lib = _load()
    if lib is None:
        return None
    if isinstance(digests, (bytes, bytearray, memoryview)):
        data = digests
        n = len(data) // 32
        if isinstance(data, bytearray):
            buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer(data)
        else:
            buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(
                data or b"\x00")
    else:
        data = b"".join(digests)
        n = len(digests)
        buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(
            data or b"\x00")
    out = (ctypes.c_uint8 * 32)()
    lib.tm_merkle_root_from_digests(buf, n, out)
    return bytes(out)


def ed25519_prepare(pk_bytes: bytes, sig_bytes: bytes,
                    msgs: List[bytes]):
    """Batched Ed25519 host prep: h = SHA512(R||A||M) mod L plus the
    s < L precheck, one C call for the whole batch. pk_bytes/sig_bytes
    are the n*32 / n*64 contiguous arrays. Returns (h_bytes, pre) as
    numpy arrays, or None when native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np
    n = len(msgs)
    if len(pk_bytes) != 32 * n or len(sig_bytes) != 64 * n:
        raise ValueError(
            f"ed25519_prepare: {n} msgs need {32 * n}/{64 * n} pk/sig "
            f"bytes, got {len(pk_bytes)}/{len(sig_bytes)}")
    buf, offsets = _pack(msgs)
    pk = (ctypes.c_uint8 * max(1, len(pk_bytes))).from_buffer_copy(
        pk_bytes or b"\x00")
    sg = (ctypes.c_uint8 * max(1, len(sig_bytes))).from_buffer_copy(
        sig_bytes or b"\x00")
    h_out = (ctypes.c_uint8 * (32 * n))()
    pre_out = (ctypes.c_uint8 * max(1, n))()
    lib.tm_ed25519_prepare(pk, sg, buf, offsets, n, h_out, pre_out)
    h = np.frombuffer(bytes(h_out), np.uint8).reshape(n, 32).copy()
    pre = np.frombuffer(bytes(pre_out), np.uint8)[:n].astype(bool).copy()
    return h, pre


def partset_build(data: bytes, part_size: int):
    """(root, [aunts per part]) for the part-size split of `data` —
    split + leaf hashing + tree + every proof in ONE C call (the
    part-set constructor's whole skeleton; types/part_set.py slices the
    payloads itself, they are views of bytes it already holds). Empty
    data yields one empty part, matching PartSet.from_data. None when
    native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    if part_size <= 0:
        raise ValueError("part_size must be positive")
    n = max(1, -(-len(data) // part_size))
    depth_max = max(1, (n - 1).bit_length()) if n > 1 else 1
    buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(
        data or b"\x00")
    out_root = (ctypes.c_uint8 * 32)()
    out_aunts = (ctypes.c_uint8 * (32 * depth_max * n))()
    depth = lib.tm_partset_build(buf, len(data), part_size,
                                 out_root, out_aunts)
    raw = bytes(out_aunts)
    proofs = []
    for i in range(n):
        base = 32 * depth * i  # C packs proofs at the actual depth
        proofs.append([raw[base + 32 * j:base + 32 * (j + 1)]
                       for j in range(depth)])
    return bytes(out_root), proofs


def merkle_tree_proofs(items: List[bytes]):
    """(root, [aunts per item]) from ONE tree build — the part-set
    constructor needs every item's proof; per-item merkle_proof calls
    rebuilt the tree once per part. None when native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(items)
    depth_max = max(1, (max(n, 1) - 1).bit_length())
    buf, offsets = _pack(items)
    out_root = (ctypes.c_uint8 * 32)()
    out_aunts = (ctypes.c_uint8 * (32 * depth_max * max(1, n)))()
    depth = lib.tm_merkle_tree_proofs(buf, offsets, n, out_root, out_aunts)
    raw = bytes(out_aunts)
    proofs = []
    for i in range(n):
        base = 32 * depth * i  # C packs proofs at the actual depth
        proofs.append([raw[base + 32 * j:base + 32 * (j + 1)]
                       for j in range(depth)])
    return bytes(out_root), proofs


# -- burst ChaCha20-Poly1305 (p2p secret-connection frame plane) ------------
# One C call seals/opens a whole burst of length-prefixed frames (GIL
# released by ctypes), replacing a Python AEAD round trip per <=1024-byte
# frame. Gated behind an RFC 8439 self-check: if the compiled kernels do
# not reproduce the §2.8.2 vector (and a burst round trip + tamper
# rejection), the loader reports unavailable and callers stay on the
# cryptography/purecrypto per-frame path.

_aead_ok: Optional[bool] = None

_RFC8439_KEY = bytes(range(0x80, 0xA0))
_RFC8439_NONCE = bytes.fromhex("070000004041424344454647")
_RFC8439_AAD = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
_RFC8439_PT = (b"Ladies and Gentlemen of the class of '99: If I could "
               b"offer you only one tip for the future, sunscreen would "
               b"be it.")
_RFC8439_CT_HEAD = bytes.fromhex("d31a8d34648e60db7b86afbc53ef7ec2")
_RFC8439_TAG = bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")


def _u8(data: bytes):
    return (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(
        data or b"\x00")


def _aead_self_check(lib) -> bool:
    try:
        # 1) RFC 8439 §2.8.2 seal vector (arbitrary nonce + aad)
        out = (ctypes.c_uint8 * (len(_RFC8439_PT) + 16))()
        lib.tm_aead_seal_one(_u8(_RFC8439_KEY), _u8(_RFC8439_NONCE),
                             _u8(_RFC8439_AAD), len(_RFC8439_AAD),
                             _u8(_RFC8439_PT), len(_RFC8439_PT), out)
        sealed = bytes(out)
        if sealed[:16] != _RFC8439_CT_HEAD or sealed[-16:] != _RFC8439_TAG:
            return False
        # 2) burst seal -> burst open round trip with counter nonces
        key = bytes(range(32))
        chunks = [b"", b"a", b"frame-two", b"x" * 1024]
        wire = _aead_seal_burst_raw(lib, key, 5, chunks)
        frames, pos = [], 0
        while pos < len(wire):
            clen = int.from_bytes(wire[pos:pos + 4], "big")
            frames.append(wire[pos + 4:pos + 4 + clen])
            pos += 4 + clen
        opened = _aead_open_burst_raw(lib, key, 5, frames)
        if opened is None or len(opened) != len(chunks):
            return False
        for chunk, plain in zip(chunks, opened):
            dlen = int.from_bytes(plain[:2], "big")
            if dlen != len(chunk) or plain[2:2 + dlen] != chunk:
                return False
        # 3) a flipped ciphertext bit must be rejected at its index
        bad = bytearray(frames[2])
        bad[0] ^= 1
        if _aead_open_burst_raw(lib, key, 5,
                                [frames[0], frames[1], bytes(bad)]) \
                is not None:
            return False
        return True
    except Exception:
        return False


def _aead_lib():
    """The hostops lib, only once the AEAD kernels passed the RFC 8439
    self-check; None otherwise."""
    global _aead_ok
    lib = _load()
    if lib is None:
        return None
    if _aead_ok is None:
        _aead_ok = _aead_self_check(lib)
    return lib if _aead_ok else None


def aead_available() -> bool:
    return _aead_lib() is not None


def aead_seal_one(key: bytes, nonce12: bytes, aad: bytes,
                  pt: bytes) -> Optional[bytes]:
    """Single seal with an arbitrary nonce — the RFC-vector surface the
    parity tests drive (the frame plane itself always uses the burst
    entry points). -> ct||tag, or None when native is unavailable."""
    lib = _aead_lib()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * (len(pt) + 16))()
    lib.tm_aead_seal_one(_u8(key), _u8(nonce12), _u8(aad), len(aad),
                         _u8(pt), len(pt), out)
    return bytes(out)


def _nonce_split(nonce_start: int):
    return nonce_start & 0xFFFFFFFFFFFFFFFF, (nonce_start >> 64) & 0xFFFFFFFF


def _aead_seal_burst_raw(lib, key: bytes, nonce_start: int,
                         chunks: List[bytes]) -> bytes:
    buf, offsets = _pack(chunks)
    total = sum(len(c) for c in chunks) + 22 * len(chunks)
    out = (ctypes.c_uint8 * max(1, total))()
    lo, hi = _nonce_split(nonce_start)
    lib.tm_aead_seal_burst(_u8(key), lo, hi, buf, offsets, len(chunks), out)
    return bytes(out)[:total]


def _aead_open_burst_raw(lib, key: bytes, nonce_start: int,
                         frames: List[bytes]) -> Optional[List[bytes]]:
    buf, offsets = _pack(frames)
    sizes = [max(0, len(f) - 16) for f in frames]
    total = sum(sizes)
    out = (ctypes.c_uint8 * max(1, total))()
    lo, hi = _nonce_split(nonce_start)
    rc = lib.tm_aead_open_burst(_u8(key), lo, hi, buf, offsets,
                                len(frames), out)
    if rc != len(frames):
        return None
    raw = bytes(out)[:total]
    plains, pos = [], 0
    for sz in sizes:
        plains.append(raw[pos:pos + sz])
        pos += sz
    return plains


def aead_seal_burst(key: bytes, nonce_start: int,
                    chunks: List[bytes]) -> Optional[bytes]:
    """Seal every chunk (payload WITHOUT its 2-byte length header) as one
    SecretConnection frame each, counter nonces from nonce_start, and
    return the concatenated wire bytes (be32 length prefix included per
    frame) — byte-identical to per-frame sealing. None when the native
    kernels are unavailable or failed their self-check."""
    lib = _aead_lib()
    if lib is None:
        return None
    return _aead_seal_burst_raw(lib, key, nonce_start, chunks)


def aead_open_burst(key: bytes, nonce_start: int,
                    frames: List[bytes]) -> Optional[List[bytes]]:
    """Open sealed frames (ct||tag each, wire length prefix stripped)
    with counter nonces from nonce_start. Returns the plaintexts (2-byte
    length header still attached), or raises AeadTagError on the first
    failing frame. None when native is unavailable."""
    lib = _aead_lib()
    if lib is None:
        return None
    out = _aead_open_burst_raw(lib, key, nonce_start, frames)
    if out is None:
        raise AeadTagError("burst frame failed AEAD authentication")
    return out


class AeadTagError(Exception):
    """A burst frame failed Poly1305 authentication."""


def merkle_proof(items: List[bytes], index: int):
    """(root, aunts) or None when native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(items)
    depth_max = max(1, (max(n, 1) - 1).bit_length())
    buf, offsets = _pack(items)
    out_root = (ctypes.c_uint8 * 32)()
    out_aunts = (ctypes.c_uint8 * (32 * depth_max))()
    depth = lib.tm_merkle_proof(buf, offsets, n, index, out_root, out_aunts)
    raw = bytes(out_aunts)
    return bytes(out_root), [raw[32 * i:32 * (i + 1)]
                             for i in range(depth)]
