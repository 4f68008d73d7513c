"""A file of key/value records: what a chain that starts with state
names in its genesis (`app_state["kvstore"]["records"]`) and every
validator loads at InitChain.

The form is length-prefixed pairs, nothing else: for each record
`uint32_le(len(key)) | key | uint32_le(len(value)) | value`. The
genesis carries the file's `count` and the `sha256` of its bytes, so
two validators given different files stop before the first block and
not at the first app hash that differs. (The snapshot plane's chunks,
storage/snapshot.py, are JSON documents of hex strings: twice the
bytes and a parse of the whole store, for a file of a gigabyte.)
"""

from __future__ import annotations

import hashlib
import struct
from typing import Iterable, Iterator, Tuple

_LEN = struct.Struct("<I")
_READ = 1 << 24


class RecordsError(ValueError):
    """The records file is not the one the genesis names."""


def write_records(path: str, pairs: Iterable[Tuple[bytes, bytes]]) -> dict:
    """Write `pairs` to `path`; returns the genesis entry that names
    the file: {"file", "count", "sha256"}."""
    sha = hashlib.sha256()
    count = 0
    pack = _LEN.pack
    with open(path, "wb") as f:
        batch = []
        for key, value in pairs:
            batch += (pack(len(key)), key, pack(len(value)), value)
            count += 1
            if len(batch) >= 1 << 14:
                blob = b"".join(batch)
                sha.update(blob)
                f.write(blob)
                batch = []
        blob = b"".join(batch)
        sha.update(blob)
        f.write(blob)
    return {"file": path, "count": count, "sha256": sha.hexdigest()}


def read_records(entry: dict) -> Iterator[Tuple[bytes, bytes]]:
    """The file's records in order. Raises RecordsError after the last
    one, before the caller can take the store as loaded, if the bytes
    do not hash to the entry's `sha256`, the records are not `count`,
    or the file ends inside a record."""
    try:
        path, want_n = entry["file"], int(entry["count"])
        want_sha = str(entry["sha256"]).lower()
    except (KeyError, TypeError, ValueError) as e:
        raise RecordsError(f"records entry needs file, count and sha256: "
                           f"{e!r}") from e
    sha = hashlib.sha256()
    count = 0
    unpack = _LEN.unpack_from
    buf = b""
    with open(path, "rb") as f:
        while True:
            chunk = f.read(_READ)
            if not chunk:
                break
            sha.update(chunk)
            buf = buf + chunk if buf else chunk
            pos, end = 0, len(buf)
            while end - pos >= 4:
                (klen,) = unpack(buf, pos)
                v_at = pos + 4 + klen
                if end - v_at < 4:
                    break
                (vlen,) = unpack(buf, v_at)
                nxt = v_at + 4 + vlen
                if nxt > end:
                    break
                yield buf[pos + 4:v_at], buf[v_at + 4:nxt]
                count += 1
                pos = nxt
            buf = buf[pos:]
    if buf:
        raise RecordsError(f"{path}: ends inside a record "
                           f"({len(buf)} bytes over)")
    if count != want_n:
        raise RecordsError(f"{path}: {count} records, the genesis says "
                           f"{want_n}")
    if sha.hexdigest() != want_sha:
        raise RecordsError(f"{path}: sha256 {sha.hexdigest()}, the genesis "
                           f"says {want_sha}")
