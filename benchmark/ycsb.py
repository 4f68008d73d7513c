"""YCSB's core workload, as far as workload A needs it, with the
standard library alone: the load generator (a process that never
imports the program), the driver and the plain reference all take the
key space, the skew and the values from here, so there is one of each.

From YCSB (Cooper et al., SoCC 2010; `CoreWorkload`,
`ZipfianGenerator`, `ScrambledZipfianGenerator`, as remembered): a
record is `fieldcount` x `fieldlength` = 1,000 bytes under a key
`user<number>`; an operation picks a RANK from a Zipfian distribution
with constant 0.99 over `recordcount` ranks (rank r with probability
1 / (r ** 0.99 * zeta(recordcount))), and the rank is scrambled over
the key space by FNV-1a 64, so the hot keys are not neighbours. This
file draws the rank by inverting the exact cumulative distribution
(YCSB's own generator is Gray et al.'s closed-form approximation of
the same law). The KVStore has no fields: a record is one value, and
an update rewrites it (`writeallfields=true`).
"""

from __future__ import annotations

import bisect
import hashlib
import itertools
import random
from array import array
from typing import Iterator, List, Tuple

ZIPFIAN_CONSTANT = 0.99
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK = (1 << 64) - 1


def fnv1a64(n: int) -> int:
    """FNV-1a 64 over the eight little-endian bytes of `n`."""
    h = _FNV_OFFSET
    for _ in range(8):
        h = ((h ^ (n & 0xFF)) * _FNV_PRIME) & _MASK
        n >>= 8
    return h


def scramble(rank: int, recordcount: int) -> int:
    """The item (0 .. recordcount - 1) that the rank (1 = the hottest)
    falls on. Not a bijection, as in YCSB: two ranks may share an
    item."""
    return fnv1a64(rank) % recordcount


def key_of(item: int) -> bytes:
    """`user` and 19 digits, the item hashed once more so that keys
    are not in order of item."""
    return b"user%019d" % (fnv1a64(item) % 10 ** 19)


class Zipfian:
    """Ranks 1 .. n with probability 1 / (r ** theta * zeta(n))."""

    def __init__(self, n: int, theta: float = ZIPFIAN_CONSTANT):
        self.n, self.theta = n, theta
        self._cum = array("d", itertools.accumulate(
            r ** -theta for r in range(1, n + 1)))
        self.zeta = self._cum[-1]

    def probability(self, rank: int) -> float:
        return rank ** -self.theta / self.zeta

    def rank(self, u: float) -> int:
        """The rank at the point `u` of [0, 1)."""
        return min(self.n, 1 + bisect.bisect_right(self._cum,
                                                   u * self.zeta))


class KeyChooser:
    """One generator for reads and updates: a seeded uniform draw, a
    Zipfian rank, the scramble, the key."""

    def __init__(self, seed, recordcount: int,
                 theta: float = ZIPFIAN_CONSTANT):
        self.recordcount = recordcount
        self.zipf = Zipfian(recordcount, theta)
        self._rng = random.Random(f"{seed}/ycsb/keys")

    def next_item(self) -> int:
        return scramble(self.zipf.rank(self._rng.random()),
                        self.recordcount)


def _blob(seed, tag: str, size: int) -> bytes:
    """Printable filler drawn once from the seed."""
    return hashlib.shake_256(f"{seed}/ycsb/{tag}".encode()).hexdigest(
        size // 2 + 1).encode()[:size]


class Values:
    """The 1,000 bytes of a record: a head that says which write it
    is (so no two writes of a run carry the same value), then a slice
    of a seeded blob. `loaded(item)` is what the store starts with,
    `update(i)` what the run's i-th operation writes."""

    def __init__(self, seed, record_bytes: int):
        self.seed, self.size = seed, record_bytes
        self._pad = _blob(seed, "values", 64 * record_bytes + 4099)

    def _fill(self, head: bytes, off: int) -> bytes:
        need = self.size - len(head)
        if need <= 0:
            return head[:self.size]
        off %= len(self._pad) - need
        return head + self._pad[off:off + need]

    def loaded(self, item: int) -> bytes:
        return self._fill(b"L%d.%d." % (self.seed, item), 31 * item)

    def update(self, i: int) -> bytes:
        return self._fill(b"U%d.%d." % (self.seed, i), 13 * i)


def records(seed, recordcount: int,
            record_bytes: int) -> Iterator[Tuple[bytes, bytes]]:
    """The loaded store, item by item."""
    values = Values(seed, record_bytes)
    for item in range(recordcount):
        yield key_of(item), values.loaded(item)


def distinct_keys(recordcount: int) -> List[bytes]:
    """Every key of the store; raises where two items share one (the
    store would then hold fewer records than the configuration says)."""
    keys = [key_of(item) for item in range(recordcount)]
    if len(set(keys)) != recordcount:
        raise ValueError(f"{recordcount} items give "
                         f"{len(set(keys))} distinct keys")
    return keys
