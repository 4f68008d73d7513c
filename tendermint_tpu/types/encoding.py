"""Canonical deterministic encoding — replaces go-wire + canonical_json.go.

The reference signs canonical JSON (types/canonical_json.go) and persists
go-wire binary. This rebuild uses ONE deterministic encoding for both:
canonical JSON — UTF-8, sorted keys, minimal separators, bytes as lowercase
hex, times as integer UNIX nanoseconds, no floats. Hashes are SHA-256 over
these bytes. Simple, reflection-free, language-portable.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any


def _canon(obj: Any) -> Any:
    if isinstance(obj, (bytes, bytearray)):
        return obj.hex()
    if isinstance(obj, dict):
        return {k: _canon(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, float):
        raise TypeError("floats are not deterministic; forbidden in canonical encoding")
    if hasattr(obj, "to_obj"):
        return _canon(obj.to_obj())
    return obj


def _pure_cdumps(obj: Any) -> bytes:
    """The specification path: _canon + json.dumps. The native encoder
    must be byte-equal to this (differential-tested in
    tests/test_native.py); it falls back here for shapes it rejects."""
    return json.dumps(_canon(obj), sort_keys=True, separators=(",", ":"),
                      ensure_ascii=False).encode()


# resolved lazily on first use: (canonical_dumps, Fallback,
# split_hex_array) once the native codec builds, False when unavailable
_native_state: Any = None


def _resolve_native():
    global _native_state
    from tendermint_tpu import native
    mod = native.codec()
    _native_state = (mod.canonical_dumps, mod.Fallback,
                     mod.split_hex_array) if mod else False
    return _native_state


def cdumps(obj: Any) -> bytes:
    """Canonical JSON bytes of a plain obj tree (dicts/lists/ints/str/
    bytes/None). Uses the native encoder (native/codec.cpp) when built —
    canonical encoding is the single hottest host operation in the sync
    loop — with automatic fallback to the pure path."""
    state = _native_state
    if state is None:
        state = _resolve_native()
    if state is not False:
        try:
            return state[0](obj)
        except state[1]:
            pass
    return _pure_cdumps(obj)


def cloads(data: bytes) -> Any:
    return json.loads(data.decode())


def cloads_hex_array(data: bytes, path: tuple) -> tuple:
    """cloads(data) for a document that holds an array of hex strings
    at the keys `path` below its root (a block's transactions: 97% of
    its bytes). -> (tree, items). Where the native decoder
    (native/codec.cpp split_hex_array) is built and sure of the
    document, items is that array as a list of bytes, filled straight
    from the wire's hex digits, and the tree holds [] in its place:
    only the rest of the document went through json.loads. Otherwise
    items is None and the tree is cloads(data), whole: the
    specification path, which also rules on whatever the decoder
    raised Fallback for (see its list there), so no document decodes
    to other items, or is accepted or refused, by one way and not the
    other."""
    state = _native_state
    if state is None:
        state = _resolve_native()
    if state is not False:
        try:
            items, rest = state[2](data, path)
        except state[1]:
            pass
        else:
            return cloads(rest), items
    return cloads(data), None


def chash(obj: Any) -> bytes:
    """SHA-256 of the canonical encoding."""
    return hashlib.sha256(cdumps(obj)).digest()


def hex_to_bytes(s: str | None) -> bytes | None:
    return None if s is None else bytes.fromhex(s)
