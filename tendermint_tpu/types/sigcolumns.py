"""SigColumns — a batch of (pubkey, msg, sig) triples held as columns.

What ValidatorSet.commit_verification_items hands the verifier for an
ed25519 set: the keys as one uint8[n,32] matrix, the signatures as the
votes' own bytes objects, the sign-bytes once per run of votes that
signed the same bytes, and an index from lane to run. To every reader it
is a read-only Sequence of triples, built on demand (`len`, slices,
indexes, iteration), so a list of triples and a SigColumns are
interchangeable wherever a batch travels; BatchVerifier alone observes
the form, and hands the columns to native.prep_columns as they are.
Numpy and nothing of the program: it lies beside the types so that they
and the verifier both import downward.
"""

from __future__ import annotations

from collections.abc import Sequence
from itertools import chain

import numpy as np


class SigColumns(Sequence):
    """n lanes. `pk`: uint8[n,32]; `sigs`: n bytes objects (any length:
    a malformed one fails its lane's precheck, as in a list of triples);
    `msgs`: the sign-bytes, one per run; `idx`: int32[n], lane -> msgs.
    Slices share `msgs` and view `pk` and `idx`."""

    __slots__ = ("pk", "sigs", "msgs", "idx")

    def __init__(self, pk: np.ndarray, sigs: list, msgs: list,
                 idx: np.ndarray):
        self.pk, self.sigs, self.msgs, self.idx = pk, sigs, msgs, idx

    def __len__(self) -> int:
        return len(self.sigs)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return SigColumns(self.pk[i], self.sigs[i], self.msgs,
                              self.idx[i])
        sig = self.sigs[i]      # raises IndexError, takes i < 0
        return self.pk[i].tobytes(), self.msgs[self.idx[i]], sig

    def __iter__(self):
        keys = self.pk.tobytes()
        return zip([keys[o:o + 32] for o in range(0, len(keys), 32)],
                   map(self.msgs.__getitem__, self.idx.tolist()),
                   self.sigs)

    @staticmethod
    def concat(batches: Sequence) -> Sequence:
        """One batch of all the lanes of `batches`, in order: columns if
        every one is columns, else the list of their triples."""
        if not all(isinstance(b, SigColumns) for b in batches):
            return list(chain.from_iterable(batches))
        if not batches:
            return []
        msgs, idx, base = [], [], 0
        for b in batches:
            idx.append(b.idx + base if base else b.idx)
            msgs += b.msgs
            base += len(b.msgs)
        return SigColumns(
            np.concatenate([b.pk for b in batches]),
            list(chain.from_iterable(b.sigs for b in batches)),
            msgs, np.concatenate(idx))
