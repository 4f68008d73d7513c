"""The authenticated state tree's plain reference: the hash
specification of docs/state.md written out with hashlib. Nothing here
imports `tendermint_tpu`.

    kh        = SHA256(key)
    leaf      = SHA256(0x00 | kh | SHA256(value))
    inner     = SHA256(0x01 | uint16_be(bit) | left | right)
    app_hash  = SHA256(0x02 | uint64_le(n_keys) | root)

The tree is a binary trie over the bits of kh (bit 0 = the high bit
of byte 0): an inner node stands at the first bit where the key hashes
under it differ, 0 to the left. Its shape follows from the key set
alone, and workload A inserts nothing, so `PlainTree` builds the shape
once, by splitting the sorted key hashes at their first differing bit,
keeps it as flat lists, and rehashes a block's leaves and the paths
above them. `verify` parses a proof's wire form (the JSON the node
returns in `response.proof`) itself and folds it up to an app hash.
"""

from __future__ import annotations

import hashlib
import json
import struct
from typing import Dict, Iterable, List, Optional, Tuple

_sha = hashlib.sha256
EMPTY_ROOT = b"\x00" * 32


def leaf_hash(kh: bytes, value: bytes) -> bytes:
    return _sha(b"\x00" + kh + _sha(value).digest()).digest()


def inner_hash(bit: int, left: bytes, right: bytes) -> bytes:
    return _sha(b"\x01" + struct.pack(">H", bit) + left + right).digest()


def app_hash_of(n_keys: int, root: bytes) -> bytes:
    return _sha(b"\x02" + struct.pack("<Q", n_keys) + root).digest()


def bit_of(kh: bytes, i: int) -> int:
    return (kh[i >> 3] >> (7 - (i & 7))) & 1


class PlainTree:
    """A fixed key set, its values, and the hashes above them.

    Nodes are numbers: leaf i (in key-hash order) is node i, inner
    nodes follow. `parent[x]` is x's parent (-1 at the root)."""

    def __init__(self, pairs: Iterable[Tuple[bytes, bytes]]):
        got = {bytes(k): bytes(v) for k, v in pairs}
        order = sorted((_sha(k).digest(), k) for k in got)
        self.keys = [k for _kh, k in order]
        self.khs = [kh for kh, _k in order]
        self.index: Dict[bytes, int] = {k: i for i, k in
                                        enumerate(self.keys)}
        self.values: List[bytes] = [got[k] for k in self.keys]
        n = self.n = len(order)
        self.hash: List[bytes] = [leaf_hash(kh, v) for kh, v in
                                  zip(self.khs, self.values)]
        self.parent = [-1] * n
        self.bit: List[int] = [0] * n       # of inner nodes; leaves: unused
        self.left: List[int] = [-1] * n
        self.right: List[int] = [-1] * n
        self.root = self._split(0, n, 0) if n else -1
        self._hash_inners()

    def _split(self, lo: int, hi: int, from_bit: int) -> int:
        """The node over the sorted key hashes [lo, hi), which agree
        on every bit before `from_bit`. Recursion is explicit: a trie
        over hashes is shallow, a trie over chosen keys need not be."""
        khs = self.khs
        # (lo, hi, from_bit, parent, is_right)
        todo = [(lo, hi, from_bit, -1, False)]
        top = -1
        while todo:
            lo, hi, bit, up, is_right = todo.pop()
            if hi - lo == 1:
                node = lo
            else:
                first, last = khs[lo], khs[hi - 1]
                while bit_of(first, bit) == bit_of(last, bit):
                    bit += 1            # sorted: the ends differ first
                # the first key hash whose bit is 1
                a, b = lo, hi - 1
                while b - a > 1:
                    mid = (a + b) // 2
                    if bit_of(khs[mid], bit):
                        b = mid
                    else:
                        a = mid
                node = len(self.parent)
                self.parent.append(-1)
                self.bit.append(bit)
                self.left.append(-1)
                self.right.append(-1)
                self.hash.append(b"")
                todo.append((lo, b, bit + 1, node, False))
                todo.append((b, hi, bit + 1, node, True))
            if up < 0:
                top = node
            else:
                self.parent[node] = up
                if is_right:
                    self.right[up] = node
                else:
                    self.left[up] = node
        return top

    def _hash_inners(self) -> None:
        # an inner node is made before its children, so the reverse of
        # the order of making hashes children first
        for node in range(len(self.parent) - 1, self.n - 1, -1):
            self.hash[node] = inner_hash(
                self.bit[node], self.hash[self.left[node]],
                self.hash[self.right[node]])

    # ------------------------------------------------------------ the store

    def app_hash(self) -> bytes:
        return app_hash_of(self.n, self.hash[self.root] if self.n
                           else EMPTY_ROOT)

    def get(self, key: bytes) -> Optional[bytes]:
        i = self.index.get(key)
        return None if i is None else self.values[i]

    def apply_block(self, txs: Iterable[bytes]) -> bytes:
        """One block's `key=value` transactions in order, every key
        one of the set; the app hash after it. A leaf is rehashed once
        a block, whatever the number of writes to it, and an inner
        node once however many dirty leaves lie under it."""
        dirty = set()
        for tx in txs:
            key, sep, value = tx.partition(b"=")
            if not sep:
                key = value = tx
            i = self.index.get(key)
            if i is None:
                raise KeyError(f"workload A inserts nothing: {key!r} is "
                               f"not one of the loaded keys")
            self.values[i] = value
            dirty.add(i)
        for i in dirty:
            self.hash[i] = leaf_hash(self.khs[i], self.values[i])
        ups = {self.parent[i] for i in dirty} - {-1}
        # parents have lower numbers than their inner children, so the
        # highest-numbered dirty inner node has no dirty node under it
        # that is still unhashed
        while ups:
            node = max(ups)
            ups.discard(node)
            self.hash[node] = inner_hash(
                self.bit[node], self.hash[self.left[node]],
                self.hash[self.right[node]])
            if self.parent[node] >= 0:
                ups.add(self.parent[node])
        return self.app_hash()

    def prove(self, key: bytes) -> bytes:
        """The wire form of the proof for `key`, present or absent."""
        kh = _sha(key).digest()
        steps = []
        node = self.root
        while node >= self.n:
            bit = self.bit[node]
            if bit_of(kh, bit):
                steps.append([bit, self.hash[self.left[node]].hex()])
                node = self.right[node]
            else:
                steps.append([bit, self.hash[self.right[node]].hex()])
                node = self.left[node]
        obj = {"key_hash": kh.hex(), "n_keys": self.n, "steps": steps,
               "present": node >= 0 and self.khs[node] == kh}
        if not obj["present"] and node >= 0:
            obj["other_key_hash"] = self.khs[node].hex()
            obj["other_value_hash"] = _sha(self.values[node]).hexdigest()
        return json.dumps(obj, sort_keys=True,
                          separators=(",", ":")).encode()


class Rejected(Exception):
    """A proof that does not bind what it was given to the app hash."""


def _hex32(obj: dict, field: str) -> bytes:
    try:
        out = bytes.fromhex(obj[field])
    except (KeyError, TypeError, ValueError) as e:
        raise Rejected(f"{field}: {e!r}") from e
    if len(out) != 32:
        raise Rejected(f"{field}: {len(out)} bytes")
    return out


def verify(proof: bytes, key: bytes, value: Optional[bytes],
           app_hash: bytes) -> bool:
    """Whether `proof` (wire bytes) binds `key` to `value` under
    `app_hash`: True for a present key with that value, False for a key
    proven absent (`value` None). Raises Rejected for everything else:
    a proof that folds to another hash, is for another key, claims the
    other thing, or is malformed."""
    try:
        obj = json.loads(bytes(proof).decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as e:
        raise Rejected(f"undecodable: {e}") from e
    if not isinstance(obj, dict):
        raise Rejected("not an object")
    kh = _sha(bytes(key)).digest()
    if _hex32(obj, "key_hash") != kh:
        raise Rejected("a proof for another key")
    n_keys, steps = obj.get("n_keys"), obj.get("steps")
    if type(n_keys) is not int or n_keys < 0 or \
            not isinstance(steps, list) or len(steps) > 256:
        raise Rejected("malformed dimensions")
    present = obj.get("present") is True
    if present:
        if value is None:
            raise Rejected("proves presence where absence was claimed")
        cur = leaf_hash(kh, bytes(value))
    else:
        if value is not None:
            raise Rejected("proves absence where a value was claimed")
        if n_keys == 0:
            if steps or app_hash_of(0, EMPTY_ROOT) != app_hash:
                raise Rejected("not the empty tree's proof")
            return False
        other = _hex32(obj, "other_key_hash")
        if other == kh:
            raise Rejected("absence proven by the key's own leaf")
        cur = _sha(b"\x00" + other +
                   _hex32(obj, "other_value_hash")).digest()
    prev = -1
    path = []
    for step in steps:
        if not isinstance(step, list) or len(step) != 2 or \
                type(step[0]) is not int or not prev < step[0] <= 255:
            raise Rejected(f"step {step!r} after bit {prev}")
        prev = step[0]
        try:
            sibling = bytes.fromhex(step[1])
        except (TypeError, ValueError) as e:
            raise Rejected(f"sibling: {e!r}") from e
        if len(sibling) != 32:
            raise Rejected("sibling of another length")
        path.append((prev, sibling))
    for bit, sibling in reversed(path):
        cur = inner_hash(bit, sibling, cur) if bit_of(kh, bit) \
            else inner_hash(bit, cur, sibling)
    if app_hash_of(n_keys, cur) != app_hash:
        raise Rejected("folds to another app hash")
    return present
