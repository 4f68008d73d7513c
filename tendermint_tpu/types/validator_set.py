"""Validator and ValidatorSet — proposer rotation + BATCHED commit verify.

Capability parity with types/validator_set.go, with the central redesign of
this framework: VerifyCommit (reference :229-273) loops one Ed25519 verify
per precommit; here all signatures of a commit are collected and dispatched
to models/verifier.BatchVerifier in ONE call — on TPU that is a single
fixed-shape kernel launch for the whole validator set (10k validators = one
batch), the north-star workload of BASELINE.json.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from tendermint_tpu import native, telemetry
from tendermint_tpu.ops import merkle
from tendermint_tpu.telemetry import trace
from tendermint_tpu.types import encoding
from tendermint_tpu.types.keys import PubKey, address_of
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.vote import VoteType, sign_bytes_template

# counted once per call of a collect phase (commit_verification_items,
# commit_lanes_by_address) that walked a commit to its end
_m_vote_walks = telemetry.counter(
    "verifier_vote_walks_total",
    "Commits whose votes a collect phase (commit_verification_items, "
    "commit_lanes_by_address) walked: native (native/prep.cpp "
    "walk_votes, one call a commit) or pure (the Python loop: no "
    "extension, or a commit the native walk declined to read)", ("how",))

_address_memo = functools.lru_cache(maxsize=65536)(address_of)


@dataclass
class Validator:
    pubkey: bytes                # 32-byte ed25519
    voting_power: int
    accum: int = 0               # proposer-priority accumulator

    @property
    def address(self) -> bytes:
        # memoized ACROSS copies: ValidatorSet construction re-sorts by
        # address and state bookkeeping copies the set several times per
        # block, so a per-instance cache still rehashed every pubkey on
        # each copy (~10 set copies x V hashes per block in fast-sync)
        return _address_memo(self.pubkey)

    def copy(self) -> "Validator":
        # __new__ + direct writes: dataclass __init__ shows up in the
        # sync-loop profile at V copies per set copy
        v = Validator.__new__(Validator)
        v.pubkey = self.pubkey
        v.voting_power = self.voting_power
        v.accum = self.accum
        return v

    def compare_accum(self, other: "Validator") -> "Validator":
        """Higher accum wins; ties break to lower address
        (types/validator.go:41)."""
        if self.accum > other.accum:
            return self
        if self.accum < other.accum:
            return other
        return self if self.address < other.address else other

    def to_obj(self):
        return {"pubkey": self.pubkey.hex(), "voting_power": self.voting_power,
                "accum": self.accum}

    @classmethod
    def from_obj(cls, o):
        return cls(bytes.fromhex(o["pubkey"]), o["voting_power"], o["accum"])


class _SetColumns(NamedTuple):
    """A set's keys and powers in validator order, built once per set."""
    pk: Optional[np.ndarray]    # uint8[V,32], read-only; None unless
    #                             every key is a 32-byte ed25519 key
    powers: np.ndarray          # int64[V], or Python ints past 2^63
    total: int


class CommitPower:
    """The `item_power` of commit_verification_items, opaque to its
    callers: per lane its validator's power and whether its vote is for
    the block, and `tally`, the exact sum of the powers for the block
    over all lanes. An invalid lane fails its commit outright, so that
    sum does not wait for the verdicts; only a lane that HAS a verdict
    counts, so a verdict vector shorter than the lanes is judged on
    `tally_of` its length."""

    __slots__ = ("powers", "for_block", "tally")

    def __init__(self, powers: np.ndarray, for_block: np.ndarray,
                 tally: int):
        self.powers, self.for_block, self.tally = powers, for_block, tally

    def tally_of(self, lanes: int) -> int:
        """The power for the block of the first `lanes` lanes, exactly."""
        return sum(self.powers[:lanes][self.for_block[:lanes]].tolist())


def _lane_runs(starts: list, n: int) -> np.ndarray:
    """int32[n]: for each lane the run it lies in, from the lanes at
    which the runs start."""
    if len(starts) == 1:
        return np.zeros(n, np.int32)
    return np.repeat(np.arange(len(starts), dtype=np.int32),
                     np.diff(starts + [n]))


def _walk_votes(pcs, height: int, round_: int, precommit: int, template):
    """THE vote walk of commit_verification_items, and the specification
    of native.walk_votes, which takes its place where the extension is
    loaded: every vote a precommit of this height and round, else the
    ValueError; -> (sigs: the votes' signature objects, msgs: a
    sign-bytes per run, idx: int32[n] lane -> msgs, for_block: bool[n],
    absent: the slots without a vote, all_for: every lane is for the
    block).

    Within one commit the votes differ in timestamp and, for nil votes,
    in block id: the sign-bytes are built once per run of votes that
    signed the same, around the ONE layout definition (`template(b)` ->
    vote.sign_bytes_template's prefix and suffix and whether `b` is the
    block, asked once per distinct block id; pinned by
    test_commit_items_sign_bytes_match). A commit's votes for one block
    share one BlockID object (as built, and as Commit.from_obj decodes);
    others compare fields."""
    sigs, absent = [], []
    msgs, msg_at = [], []       # a run's sign-bytes, its first lane
    flags, flag_at = [], []     # for_block of a run of one block id
    known = {}          # block id fields -> (prefix, suffix, for_block)
    bid = ts = bhash = ptotal = phash = pre = suf = None
    for pc in pcs:
        if pc is None:
            absent.append(len(sigs) + len(absent))
            continue
        if pc.type != precommit:
            raise ValueError("commit contains non-precommit")
        if pc.height != height or pc.round != round_:
            raise ValueError("commit vote height/round mismatch")
        b = pc.block_id
        if b is not bid:
            bid, parts = b, b.parts
            if b.hash != bhash or parts.total != ptotal \
                    or parts.hash != phash:
                bhash, ptotal, phash = key = \
                    b.hash, parts.total, parts.hash
                t = known.get(key)
                if t is None:
                    t = known[key] = template(b)
                pre, suf, for_block = t
                flags.append(for_block)
                flag_at.append(len(sigs))
                ts = None       # other sign-bytes too
        if pc.timestamp_ns != ts:
            ts = pc.timestamp_ns
            msgs.append((pre + str(ts) + suf).encode())
            msg_at.append(len(sigs))
        sigs.append(pc.signature)
    n = len(sigs)
    if len(flags) == 1:
        for_block = np.empty(n, np.bool_)
        for_block.fill(flags[0])
    else:
        for_block = np.array(flags, np.bool_)[_lane_runs(flag_at, n)]
    return (sigs, msgs, _lane_runs(msg_at, n), for_block, absent,
            all(flags))


class ValidatorSet:
    """Sorted-by-address validator array with accum-based proposer rotation
    (types/validator_set.go:24-71)."""

    def __init__(self, validators: Sequence[Validator],
                 _fresh: bool = True):
        self.validators: List[Validator] = sorted(
            (v.copy() for v in validators), key=lambda v: v.address)
        addrs = [v.address for v in self.validators]
        if len(set(addrs)) != len(addrs):
            raise ValueError("duplicate validator address")
        # addr -> index map: the reference binary-searches its sorted
        # array (types/validator_set.go:93-101); lookups here are per
        # vote on the Python hot path, so O(1) beats O(log V). The
        # ordering never changes after construction (updates build a
        # new set), so the map cannot go stale.
        self._index = {a: i for i, a in enumerate(addrs)}
        self._proposer: Optional[Validator] = None
        self._hash: Optional[bytes] = None
        self._columns: Optional[_SetColumns] = None
        # NewValidatorSet parity (types/validator_set.go:33-48): a FRESH
        # set runs one accum increment, so the first proposer is the
        # highest-power validator, not the lowest address. Deserialized
        # sets (from_obj) and update_with_changes suppress this — they
        # carry accums mid-rotation, exactly like the reference's
        # reflect-deserialization and Add/Update/Remove paths, where the
        # per-block increment happens in ApplyBlock instead.
        if _fresh and self.validators:
            self.increment_accum(1)

    def __len__(self) -> int:
        return len(self.validators)

    def copy(self) -> "ValidatorSet":
        # fast path: a copy has identical addresses in identical order
        # (updates construct NEW sets through __init__), so the sorted
        # order, duplicate check and addr->index map carry over — the
        # index dict is shared, which is safe because nothing mutates a
        # set's membership in place
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = [v.copy() for v in self.validators]
        vs._index = self._index
        vs._proposer = self._proposer.copy() if self._proposer else None
        vs._hash = self._hash
        vs._columns = self._columns
        return vs

    def total_voting_power(self) -> int:
        return sum(v.voting_power for v in self.validators)

    def get_by_address(self, addr: bytes):
        i = self._index.get(addr, -1)
        return (i, self.validators[i]) if i >= 0 else (-1, None)

    def get_by_index(self, i: int) -> Optional[Validator]:
        return self.validators[i] if 0 <= i < len(self.validators) else None

    def has_address(self, addr: bytes) -> bool:
        return self.get_by_address(addr)[0] >= 0

    # -- proposer rotation (types/validator_set.go:51-71) ------------------

    def increment_accum(self, times: int = 1) -> None:
        """Advance proposer rotation by `times` rounds — reference-exact
        (types/validator_set.go:51-71): power*times lands on every accum
        ONCE, then the running maximum is decremented `times` times (the
        last pick is the proposer). Decrement-per-step over freshly
        re-added power picks DIFFERENT proposers for times > 1, which is
        a live round-skip (consensus enter_new_round jumping rounds)."""
        if not self.validators or times <= 0:
            return
        for v in self.validators:
            v.accum += v.voting_power * times
        total = self.total_voting_power()
        for _ in range(times):
            mostest = self.validators[0]
            for v in self.validators[1:]:
                mostest = mostest.compare_accum(v)
            mostest.accum -= total
        self._proposer = mostest

    def proposer(self) -> Validator:
        if self._proposer is None:
            mostest = self.validators[0]
            for v in self.validators[1:]:
                mostest = mostest.compare_accum(v)
            self._proposer = mostest
        return self._proposer

    # -- hashing ------------------------------------------------------------

    def hash(self) -> bytes:
        """Merkle root over (pubkey, power) leaves. Cached: membership
        and powers never mutate in place (update_with_changes builds a
        NEW set; increment_accum only moves accums, which are excluded
        from the hash) — and callers hash the same set per header
        (lite certify does so 3x per header)."""
        if self._hash is None:
            leaves = [encoding.cdumps(
                {"pubkey": v.pubkey.hex(), "voting_power": v.voting_power})
                for v in self.validators]
            self._hash = merkle.root_host(leaves)
        return self._hash

    def columns(self) -> _SetColumns:
        """Keys and powers as columns; cached beside `_hash`, and for
        its reason."""
        if self._columns is None:
            keys = [v.pubkey for v in self.validators]
            powers = [v.voting_power for v in self.validators]
            pk = None
            if all(type(k) is bytes and len(k) == 32 for k in keys):
                pk = np.frombuffer(b"".join(keys), np.uint8).reshape(-1, 32)
            try:
                vec = np.array(powers, np.int64)
            except OverflowError:
                vec = np.array(powers, object)
            vec.flags.writeable = False
            self._columns = _SetColumns(pk, vec, sum(powers))
        return self._columns

    def to_obj(self):
        o = {"validators": [v.to_obj() for v in self.validators]}
        # The proposer is STATE, not derivable from accums: after an
        # increment the proposer is the pre-decrement maximum, which the
        # post-decrement accums no longer identify. The reference
        # persists its Proposer field via reflect for the same reason —
        # without it, a restarted node computes a different proposer
        # than its live peers and stalls its first post-restart height.
        if self._proposer is not None:
            o["proposer"] = self._proposer.address.hex()
        return o

    @classmethod
    def from_obj(cls, o):
        vs = cls([Validator.from_obj(v) for v in o["validators"]],
                 _fresh=False)
        prop = o.get("proposer")
        if prop is not None:
            i = vs._index.get(bytes.fromhex(prop), -1)
            if i < 0:
                # inconsistent persisted state: failing loudly beats
                # silently deriving a proposer live peers won't agree on
                raise ValueError(
                    f"proposer {prop} not in validator set")
            vs._proposer = vs.validators[i]
        return vs

    # -- commit verification: THE batched hot path --------------------------

    def _walk_commit(self, chain_id: str, block_id, height: int, commit):
        """What both collect phases read of a commit, whatever its
        size: the height and round rules and one sign-bytes per run of
        votes, as `_walk_votes` gives them."""
        pcs = commit.precommits
        if height != commit.height():
            raise ValueError("commit height mismatch")
        round_ = commit.round()
        precommit = VoteType.PRECOMMIT

        def template(b):
            return sign_bytes_template(chain_id, b, height, round_,
                                       precommit) + (b == block_id,)

        # one native call a commit where the extension is loaded; the
        # Python loop where it is not, and for whatever commit the
        # native walk declines to read
        how = "native"
        walked = native.walk_votes(pcs, height, round_, precommit,
                                   template)
        if walked is None:
            how = "pure"
            walked = _walk_votes(pcs, height, round_, precommit, template)
        if telemetry.enabled():
            _m_vote_walks.labels(how).inc()
        return walked

    def commit_verification_items(self, chain_id: str, block_id,
                                  height: int, commit):
        """Collect phase of verify_commit: structural checks, then the
        commit's signatures as `(items, item_power)`. `items` is a batch
        for BatchVerifier.verify_async: a SigColumns for an ed25519
        set, else the list of (pubkey, sign_bytes, sig) triples;
        `item_power` goes to check_commit_results with the verdicts.
        Split out so fast-sync and the lite client pool the batches of
        MANY blocks into one device dispatch (blockchain/reactor.go:286's
        per-block loop becomes one TPU dispatch per window)."""
        pcs = commit.precommits
        if len(self.validators) != len(pcs):
            raise ValueError(
                f"commit size {len(pcs)} != valset size {len(self.validators)}")
        sigs, msgs, idx, for_block, absent, all_for = self._walk_commit(
            chain_id, block_id, height, commit)

        cols = self.columns()
        if absent:
            rows = np.delete(np.arange(len(pcs)), absent)
            powers = cols.powers[rows]
        else:
            rows, powers = slice(None), cols.powers
        if not absent and all_for:
            tally = cols.total
        else:
            tally = sum(powers[for_block].tolist())
        if cols.pk is not None:
            items = SigColumns(cols.pk[rows], sigs, msgs, idx)
        else:       # secp256k1 keys: host-verified, as triples
            keys = [v.pubkey for v, pc in zip(self.validators, pcs)
                    if pc is not None]
            items = list(zip(keys, map(msgs.__getitem__, idx.tolist()),
                             sigs))
        return items, CommitPower(powers, for_block, tally)

    def commit_lanes_by_address(self, chain_id: str, block_id,
                                height: int, commit, strangers=None):
        """Collect phase for a commit that is NOT this set's to judge:
        fast-sync's window is collected under the set held then, and a
        block's own set, the one in force when it applies, may hold
        other members, in other slots, and more or fewer of them. The
        walk of commit_verification_items (its height and round rules,
        its sign-bytes) without the size rule and without this set's
        stake: `(items, for_block)`, one lane per vote that is there,
        in order, each under the key of `rows_by_address`; `items` in
        commit_verification_items' two forms, `for_block` the flags its
        CommitPower carries; `strangers` is `rows_by_address`'s. What
        judges them is check_commit_lanes under the set in force, which
        takes a verdict only for the key it was computed under."""
        sigs, msgs, idx, for_block, _absent, _all_for = self._walk_commit(
            chain_id, block_id, height, commit)
        rows = self.rows_by_address(commit, strangers)
        cols = self.columns()
        if cols.pk is not None:
            return SigColumns(cols.pk[rows], sigs, msgs, idx), for_block
        vals = self.validators
        return list(zip([vals[r].pubkey for r in rows],
                        map(msgs.__getitem__, idx.tolist()), sigs)), for_block

    def check_commit_results(self, ok, item_power) -> None:
        """Judge phase of verify_commit: every signature valid and +2/3
        power on the block. `ok`: the verdicts of the commit's lanes, a
        numpy array or a list. Raises ValueError on failure."""
        lanes = len(item_power.powers)
        if len(ok) > lanes or \
                not (ok.all() if isinstance(ok, np.ndarray) else all(ok)):
            raise ValueError("invalid signature in commit")
        # (votes for other/nil blocks count toward liveness but not quorum,
        # matching the reference's treatment of nil precommits in commits)
        power_for_block = item_power.tally if len(ok) == lanes \
            else item_power.tally_of(len(ok))
        total = self.columns().total
        if not power_for_block * 3 > total * 2:
            raise ValueError(
                f"insufficient voting power: {power_for_block}/{total}")

    def rows_by_address(self, commit, strangers=None) -> list:
        """For each vote `commit` holds, in order, the slot at which
        THIS set has the vote's `validator_address`: the rows of
        `columns().pk` under which a set that is not the commit's own
        has the best chance of verifying each vote under the key its
        own set holds for it. An address this set does not know (a
        validator that joined since) gets a row all the same, so that a
        commit brings one lane a vote whatever set pairs it: the vote's
        own slot, or this set's last where the commit is the larger.
        That lane's verdict is for a key the vote's set does not hold
        there, and check_commit_lanes verifies the vote again, unless
        its caller has by then: a dict given as `strangers` is told
        each such address and the lane that claims it (the last, where
        two do), for whoever learns the address's key before the commit
        is judged. The address is the vote's claim and only chooses the
        key that is tried: check_commit_lanes believes a verdict for
        the key it was computed under and for no other."""
        known, last = self._index.get, len(self.validators) - 1
        rows = []
        for i, pc in enumerate(commit.precommits):
            if pc is None:
                continue
            row = known(pc.validator_address)
            if row is None:
                row = min(i, last)
                if strangers is not None:
                    strangers[pc.validator_address] = len(rows)
            rows.append(row)
        return rows

    def check_commit_lanes(self, commit, lanes, ok, for_block,
                           verifier) -> int:
        """Judge phase of verify_commit for a commit whose lanes were
        paired with their keys by ANOTHER set (fast-sync collects a
        window under the set it holds then; this set is the one in
        force when the block is applied). `lanes`: the commit's batch
        as it was verified, one (key, sign-bytes, signature) per vote
        that is there, a SigColumns or a list; `ok`: their verdicts;
        `for_block`: the flags of that collection's CommitPower, which
        depend on the block id alone.

        A verdict stands for its triple: a lane whose key is the key
        this set holds at the vote's slot keeps its verdict, every other
        lane is verified now under that key, all of the commit's in one
        call on `verifier`. Then every signature valid and +2/3 of THIS
        set's stake on the block: accepts and refuses exactly as
        verify_commit under this set, with its messages (the structural
        checks, which no set but by its size decides, were the
        collection's). Returns how many lanes were verified again. A
        lane without a verdict (`ok` shorter than `lanes`) gets none
        here either, and counts as check_commit_results counts it."""
        pcs, vals = commit.precommits, self.validators
        if len(vals) != len(pcs):
            raise ValueError(
                f"commit size {len(pcs)} != valset size {len(vals)}")
        cols = self.columns()
        whole = len(lanes) == len(pcs)
        slots = range(len(pcs)) if whole else \
            [i for i, pc in enumerate(pcs) if pc is not None]
        rows = slice(None) if whole else slots
        if cols.pk is not None and isinstance(lanes, SigColumns):
            stale = np.flatnonzero(
                (lanes.pk != cols.pk[rows]).any(axis=1)).tolist()
        else:
            stale = [i for i, (s, lane) in enumerate(zip(slots, lanes))
                     if vals[s].pubkey != lane[0]]
        stale = [i for i in stale if i < len(ok)]
        if stale:
            again = verifier.verify(
                [(vals[slots[i]].pubkey,) + lanes[i][1:] for i in stale])
            ok = np.array(ok, np.bool_)
            ok[stale] = again
        powers = cols.powers[rows]
        tally = cols.total if whole and for_block.all() \
            else sum(powers[for_block].tolist())
        self.check_commit_results(ok, CommitPower(powers, for_block, tally))
        return len(stale)

    def joined_since(self, before: "ValidatorSet") -> list:
        """The members of this set under an address that `before` does
        not hold: who joined, or took a member's place, on the way from
        `before` to this set. A set that shares `before`'s members (its
        copy, as apply_block hands on a set no update touched) answers
        from one identity comparison."""
        if self._index is before._index:
            return []
        return [self.validators[self._index[a]]
                for a in self._index.keys() - before._index.keys()]

    def endorsement(self, signing: "ValidatorSet", chain_id: str,
                    block_id, commit):
        """This (trusted) set's side of a change of set between adjacent
        heights: the second tally over a commit that `signing`, the set
        the header names, has been given to verify. Returns `(power,
        extra)`: the stake THIS set holds among the commit's votes for
        `block_id`, each validator it knows (by the vote's address)
        counted once, and `extra`, the triples that the lanes of
        commit_verification_items under `signing` do not cover.

        A vote in slot i is verified there under `signing`'s key i; the
        trust-level rule (later Tendermint's light client, trust level
        1/3) verifies it under the key THIS set holds for the vote's
        address. Wherever the two keys are one, which is every vote of a
        well-formed commit, the lane's verdict serves both and nothing
        is verified twice; a vote that claims another validator's
        address is verified once more, under that key, as a triple of
        `extra`. The caller judges with check_endorsement once every
        lane of the commit has verified."""
        known, vals, slots = self._index.get, self.validators, \
            signing.validators
        power, extra, seen = 0, [], set()
        for i, pc in enumerate(commit.precommits):
            if pc is None:
                continue
            b = pc.block_id
            if b is not block_id and b != block_id:
                continue        # counts for nothing, as in verify_commit
            oi = known(pc.validator_address, -1)
            if oi < 0 or oi in seen:
                continue        # unknown to this set, or a duplicate
            seen.add(oi)
            ov = vals[oi]
            if slots[i].pubkey != ov.pubkey:
                extra.append((ov.pubkey, pc.sign_bytes(chain_id),
                              pc.signature))
            power += ov.voting_power
        return power, extra

    def check_endorsement(self, power: int, extra_ok=()) -> None:
        """Judge phase of `endorsement`: every extra triple valid and
        STRICTLY more than 1/3 of this set's stake behind the block, so
        that under the <1/3-byzantine assumption at least one honest
        validator of this set vouches for the set that follows. Raises
        ValueError."""
        if not all(extra_ok):
            raise ValueError("invalid signature in commit")
        total = self.columns().total
        if not power * 3 > total:
            raise ValueError(
                f"insufficient trusted-set endorsement: got {power}, "
                f"need > {total / 3:g} (1/3 of trusted power)")

    def verify_commit_async(self, chain_id: str, block_id, height: int,
                            commit, verifier=None):
        """Dispatch phase of verify_commit WITHOUT blocking: structural
        checks + signature dispatch run now (raising ValueError on
        structural failure immediately), and the returned zero-arg
        finisher completes the power check — raising exactly what
        verify_commit would. Opt-in async path: lets fast-sync/replay
        overlap device crypto with host work; a commit small enough to
        be the host's is verified inside the finisher
        (BatchVerifier.verify_async)."""
        from tendermint_tpu.models.verifier import default_verifier
        verifier = verifier or default_verifier()
        with trace.span("commit.collect", req=height):
            items, item_power = self.commit_verification_items(
                chain_id, block_id, height, commit)
        resolve_ok = verifier.verify_async(items)

        def finish() -> None:
            with trace.span("commit.wait", req=height):
                ok = resolve_ok()
            with trace.span("commit.check", req=height):
                self.check_commit_results(ok, item_power)

        return finish

    def verify_commit(self, chain_id: str, block_id, height: int, commit,
                      verifier=None) -> None:
        """Verify that +2/3 of this set signed the commit.

        Reference semantics (types/validator_set.go:229-273): size match,
        height match, per-vote sanity, then signature verification and
        power counting — but the signatures are verified as ONE batch.
        Raises ValueError on failure.
        """
        self.verify_commit_async(chain_id, block_id, height, commit,
                                 verifier=verifier)()

    def verify_commit_any(self, new_set: "ValidatorSet", chain_id: str,
                          block_id, height: int, commit, verifier=None) -> None:
        """Lite-client valset-transition check — reference parity with
        types/validator_set.go:288-353 VerifyCommitAny, including its
        STRICT >2/3 OLD-set threshold (:345-347; round 2 shipped a 1/3
        rule, the later-Tendermint light-client model — v0.16 is
        stricter, and this build pins the v0.16 rule with tests):

        - only votes for `block_id` count (:319, not an error otherwise)
        - each counted vote is verified against THIS (old, trusted)
          set's pubkey, looked up by the vote's validator address;
          validators unknown to the old set are SKIPPED entirely —
          never verified, never counted (:322-327)
        - duplicate addresses count once (:327 `seen`)
        - new-set power accrues only where the new validator at that
          commit index carries the SAME pubkey (:337-341)
        - accept iff old_power > 2/3 of the old total AND
          new_power > 2/3 of the new total (:345-350)

        Signatures still go through the verifier as ONE batch.
        Raises ValueError on failure."""
        from tendermint_tpu.models.verifier import default_verifier
        verifier = verifier or default_verifier()
        if len(new_set.validators) != commit.size():
            raise ValueError("commit size != new valset size")
        if height != commit.height():
            raise ValueError("commit height mismatch")

        items = []
        meta = []  # (old_power, new_power_if_same_pubkey)
        seen = set()
        round_ = commit.round()
        for idx, pc in enumerate(commit.precommits):
            if pc is None:
                continue
            if pc.type != VoteType.PRECOMMIT or pc.height != height \
                    or pc.round != round_:
                raise ValueError("bad commit vote")
            if pc.block_id != block_id:
                continue  # not an error, but doesn't count
            oi, ov = self.get_by_address(pc.validator_address)
            if ov is None or oi in seen:
                continue  # unknown to the trusted set, or double vote
            seen.add(oi)
            nv = new_set.validators[idx]
            items.append((ov.pubkey, pc.sign_bytes(chain_id), pc.signature))
            meta.append((ov.voting_power,
                         nv.voting_power if nv.pubkey == ov.pubkey else 0))
        ok = verifier.verify(items)
        old_power = new_power = 0
        for valid, (opow, npow) in zip(ok, meta):
            if not valid:
                raise ValueError("invalid signature in commit")
            old_power += opow
            new_power += npow
        if not old_power * 3 > self.total_voting_power() * 2:
            raise ValueError(
                f"insufficient old-set (trusted) voting power: got "
                f"{old_power}, need > {self.total_voting_power() * 2 / 3:g}")
        if not new_power * 3 > new_set.total_voting_power() * 2:
            raise ValueError(
                f"insufficient new-set voting power: got {new_power}, "
                f"need > {new_set.total_voting_power() * 2 / 3:g}")

    # -- updates -------------------------------------------------------------

    def update_with_changes(self, changes: Sequence[Validator]) -> "ValidatorSet":
        """Apply ABCI validator updates: power 0 removes, else add/replace
        (state/execution.go:246 semantics). Returns a new set."""
        by_addr = {v.address: v.copy() for v in self.validators}
        for c in changes:
            if c.voting_power < 0:
                raise ValueError("negative voting power")
            if c.voting_power == 0:
                if c.address not in by_addr:
                    raise ValueError("removing unknown validator")
                del by_addr[c.address]
            else:
                prev = by_addr.get(c.address)
                accum = prev.accum if prev else 0
                by_addr[c.address] = Validator(c.pubkey, c.voting_power, accum)
        if not by_addr:
            raise ValueError("validator set would be empty")
        # _fresh=False: accums carry over mid-rotation (the reference's
        # Add/Update/Remove invalidate Proposer but never re-increment;
        # ApplyBlock's own increment_accum(1) follows separately)
        return ValidatorSet(list(by_addr.values()), _fresh=False)
