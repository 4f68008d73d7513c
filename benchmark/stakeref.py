"""The plain reference for a net whose validators hold unequal stake:
who proposes, and what a commit's precommits weigh. Nothing here
imports `tendermint_tpu`.

    validators  [(address, pubkey32, voting_power)], any order

**Proposer rotation** (Tendermint v0.16 `types/validator_set.go`
`IncrementAccum`, `types/validator.go` `CompareAccum`): every validator
carries an accumulator, 0 at genesis. One step adds each validator's
power to its accumulator, takes the validator with the largest
accumulator (ties go to the lower address), makes it the proposer and
subtracts the total power from its accumulator. `IncrementAccum(times)`
adds `power * times` first and then takes and subtracts `times` times;
the last taken is the proposer. `NewValidatorSet` runs one step, so the
proposer of height 1, round 0 is the largest stake. Applying a block
runs one more step (`state/execution.go`), whatever round committed it;
a round r above 0 of a height is proposed by who a COPY of the height's
set gives after `IncrementAccum(r)` (`consensus/state.go`
`enterNewRound`), and that copy is thrown away.

**The stake of a commit**: the sum of the powers of the validators
whose precommit for the block is in it; accepted iff three times that
is more than twice the total (upstream's `> total * 2 / 3` in whole
numbers). Signatures are `commitref.verify_commit`'s business (OpenSSL,
one at a time); `tally` only weighs.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

Validator = Tuple[bytes, bytes, int]        # address, pubkey, power


class Rotation:
    """The accumulators of one validator set, stepped as upstream steps
    them."""

    def __init__(self, validators: Sequence[Validator]):
        # upstream keeps the set sorted by address and scans it in that
        # order; the order decides nothing but is kept for the reader
        self.vals = sorted((addr, power) for addr, _pub, power in validators)
        self.total = sum(power for _addr, power in self.vals)
        self.accum: Dict[bytes, int] = {addr: 0 for addr, _p in self.vals}
        self.proposer: Optional[bytes] = None
        self.increment(1)                   # NewValidatorSet

    def copy(self) -> "Rotation":
        other = Rotation.__new__(Rotation)
        other.vals, other.total = self.vals, self.total
        other.accum = dict(self.accum)
        other.proposer = self.proposer
        return other

    def increment(self, times: int) -> None:
        if times <= 0:
            return
        for addr, power in self.vals:
            self.accum[addr] += power * times
        for _ in range(times):
            # the largest accumulator; of equals, the lower address
            best = min(self.accum, key=lambda a: (-self.accum[a], a))
            self.accum[best] -= self.total
            self.proposer = best


def proposers(validators: Sequence[Validator],
              rounds: Sequence[int]) -> List[bytes]:
    """The address that proposes the block that commits height h =
    1, 2, ..., given the round `rounds[h - 1]` in which each height
    committed."""
    rot = Rotation(validators)
    out = []
    for r in rounds:
        at = rot
        if r > 0:
            at = rot.copy()
            at.increment(r)
        out.append(at.proposer)
        rot.increment(1)                    # the block is applied
    return out


def tally(validators: Sequence[Validator],
          signers: Sequence[bytes]) -> Tuple[int, int, bool]:
    """(stake of `signers` (addresses, each counted once), total stake,
    whether that is more than two thirds)."""
    power = {addr: p for addr, _pub, p in validators}
    got = sum(power[a] for a in set(signers))
    total = sum(power.values())
    return got, total, 3 * got > 2 * total


def smallest(validators: Sequence[Validator], k: int) -> List[bytes]:
    """Addresses of the k validators of least stake (ties by address)."""
    ranked = sorted(validators, key=lambda v: (v[2], v[0]))
    return [addr for addr, _pub, _p in ranked[:k]]


def largest(validators: Sequence[Validator], k: int) -> List[bytes]:
    """Addresses of the k validators of most stake (ties by address)."""
    ranked = sorted(validators, key=lambda v: (-v[2], v[0]))
    return [addr for addr, _pub, _p in ranked[:k]]
