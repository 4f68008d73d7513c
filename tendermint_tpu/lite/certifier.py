"""Certifiers — the light-client trust ladder (lite/).

StaticCertifier   — fixed valset, certify one height
                    (lite/static_certifier.go:22,57)
DynamicCertifier  — follows valset changes via verify_commit_any
                    (lite/dynamic_certifier.go:20,70)
InquiringCertifier— auto-updates through a Provider with BISECTION over
                    heights when the valset moved too far at once
                    (lite/inquiring_certifier.go:15,67,137-163)

ContinuousCertifier— tracks a CHURNING valset height by height:
                    sequential certify/update across every valset
                    delta, never skipping a height — the chaos
                    monitor's continuous-certification invariant
                    (every committed height provably safe for a light
                    client following the chain live).

certify_chain     — the TPU batch path: certify a whole run of
                    consecutive FullCommits with ONE pooled signature
                    dispatch (BASELINE.json config 5's workload).
"""

from __future__ import annotations

import time
from typing import List, Optional

from tendermint_tpu.lite.types import (
    CertificationError,
    FullCommit,
    SignedHeader,
    ValidatorsChangedError,
)
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.validator_set import ValidatorSet


class StaticCertifier:
    """Trusts exactly one validator set forever."""

    def __init__(self, chain_id: str, validators: ValidatorSet,
                 verifier=None):
        self.chain_id = chain_id
        self.validators = validators
        self.verifier = verifier

    def certify(self, fc: FullCommit) -> None:
        fc.validate_basic(self.chain_id)
        if fc.validators.hash() != self.validators.hash():
            raise ValidatorsChangedError(
                "signed by a different validator set")
        sh = fc.signed_header
        try:
            self.validators.verify_commit(
                self.chain_id, sh.block_id, sh.height, sh.commit,
                verifier=self.verifier)
        except ValueError as e:
            raise CertificationError(str(e)) from e


class DynamicCertifier:
    """Static + `update`: accept a new valset when +2/3 of it signed AND
    +2/3 of the currently-trusted set signed (verify_commit_any, the
    v0.16 rule — types/validator_set.go:345-347)."""

    def __init__(self, chain_id: str, validators: ValidatorSet,
                 height: int = 0, verifier=None):
        self.chain_id = chain_id
        self.validators = validators
        self.last_height = height
        self.verifier = verifier

    def certify(self, fc: FullCommit) -> None:
        fc.validate_basic(self.chain_id)
        if fc.validators.hash() != self.validators.hash():
            raise ValidatorsChangedError(
                "validator set changed; call update() through "
                "intermediate commits")
        StaticCertifier(self.chain_id, self.validators,
                        self.verifier).certify(fc)

    def update(self, fc: FullCommit) -> None:
        """lite/dynamic_certifier.go:70 Update."""
        fc.validate_basic(self.chain_id)
        if fc.height <= self.last_height:
            raise CertificationError(
                f"update height {fc.height} <= trusted {self.last_height}")
        sh = fc.signed_header
        try:
            self.validators.verify_commit_any(
                fc.validators, self.chain_id, sh.block_id, sh.height,
                sh.commit, verifier=self.verifier)
        except ValueError as e:
            raise CertificationError(str(e)) from e
        self.validators = fc.validators
        self.last_height = fc.height


class InquiringCertifier:
    """DynamicCertifier + a Provider to fetch missing FullCommits,
    bisecting when a direct update is rejected
    (lite/inquiring_certifier.go:137-163)."""

    def __init__(self, chain_id: str, trusted: FullCommit, provider,
                 verifier=None):
        self.chain_id = chain_id
        self.provider = provider
        self.cert = DynamicCertifier(chain_id, trusted.validators,
                                     trusted.height, verifier=verifier)
        provider.store_commit(trusted)

    @property
    def last_height(self) -> int:
        return self.cert.last_height

    def certify(self, fc: FullCommit) -> None:
        if fc.validators.hash() != self.cert.validators.hash():
            self._update_to_hash_or_height(fc)
        self.cert.certify(fc)
        self.provider.store_commit(fc)

    def _update_to_hash_or_height(self, fc: FullCommit) -> None:
        """Walk trust from last_height to fc.height via update(); on an
        'insufficient old-set power' rejection, bisect the height range
        and trust the midpoint first."""
        self._update_to(fc, depth=0)

    def _update_to(self, fc: FullCommit, depth: int) -> None:
        if depth > 64:
            raise CertificationError("bisection too deep")
        try:
            self.cert.update(fc)
            self.provider.store_commit(fc)
            return
        except CertificationError:
            pass
        lo, hi = self.cert.last_height, fc.height
        if hi - lo <= 1:
            raise CertificationError(
                f"cannot bridge trust from {lo} to {hi}")
        mid_h = (lo + hi) // 2
        mid = self.provider.get_by_height(mid_h)
        if mid is None:
            raise CertificationError(f"provider has no commit <= {mid_h}")
        if mid.height <= lo:
            raise CertificationError(
                f"cannot bridge trust: no commits in ({lo}, {mid_h}]")
        self._update_to(mid, depth + 1)
        self._update_to(fc, depth + 1)


def _trusted_set_endorsement(trusted: ValidatorSet, chain_id: str,
                             block_id, height: int, commit,
                             verifier=None) -> None:
    """Trust-level endorsement for a valset transition (the later-
    Tendermint light-client rule, trust_level = 1/3): among the
    commit's votes for `block_id`, those cast by validators the
    TRUSTED set knows must verify and carry STRICTLY more than 1/3 of
    the trusted set's power — under the <1/3-byzantine assumption at
    least one honest trusted validator vouches for the new set.
    Raises ValueError. Used by ContinuousCertifier, whose transitions
    are single EndBlock deltas; the v0.16 VerifyCommitAny overlap rule
    (DynamicCertifier.update) remains the JUMP bridge — it counts only
    overlap validators toward the new set, which rejects honest
    quorum-sparse commits the moment one validator joins or leaves."""
    from tendermint_tpu.models.verifier import default_verifier
    verifier = verifier or default_verifier()
    items = []
    powers = []
    seen = set()
    for pc in commit.precommits:
        if pc is None or pc.block_id != block_id:
            continue
        oi, ov = trusted.get_by_address(pc.validator_address)
        if ov is None or oi in seen:
            continue  # unknown to the trusted set, or duplicate
        seen.add(oi)
        items.append((ov.pubkey, pc.sign_bytes(chain_id), pc.signature))
        powers.append(ov.voting_power)
    old_power = 0
    for valid, power in zip(verifier.verify(items), powers):
        if not valid:
            raise ValueError("invalid signature in commit")
        old_power += power
    total = trusted.total_voting_power()
    if not old_power * 3 > total:
        raise ValueError(
            f"insufficient trusted-set endorsement: got {old_power}, "
            f"need > {total / 3:g} (1/3 of trusted power)")


class ContinuousCertifier:
    """Certify EVERY height of a chain whose valset churns, in order.

    Per height: same valset hash as trusted -> plain certify (pooled
    batch verify). Changed hash -> the adjacent-height transition
    rule: (1) the commit must carry +2/3 of the NEW (signing) set —
    ordinary verify_commit, every signer counted; (2) the TRUSTED set
    must endorse it with >1/3 of its own power among the signers it
    knows (_trusted_set_endorsement — the later-Tendermint light-
    client trust level, sound because <1/3 byzantine means at least
    one honest trusted validator signed the new set into power).

    It NEVER skips a height — feeding a non-consecutive height raises
    immediately; bridging a gap is DynamicCertifier.update /
    InquiringCertifier bisection territory, whose strict v0.16 rule
    refuses any jump that moved more than 1/3 of the trusted power
    (test-pinned). `trusted` is the valset expected to sign
    `next_height` (genesis set for next_height=1)."""

    def __init__(self, chain_id: str, trusted: ValidatorSet,
                 next_height: int = 1, verifier=None):
        self.chain_id = chain_id
        self.verifier = verifier
        self.validators = trusted
        self.next_height = next_height
        self.static_certified = 0
        self.updates = 0          # heights crossed via a valset delta
        # recently certified headers' app hashes, keyed by height — the
        # anchor a per-key STATE proof verifies against (header h binds
        # the app state after block h-1). Bounded: certified reads only
        # ever need the frontier's neighborhood.
        self.app_hashes: dict = {}

    @property
    def certified_height(self) -> int:
        return self.next_height - 1

    def advance(self, fc: FullCommit) -> None:
        """Certify fc (which must be the next height) and advance
        trust. Raises CertificationError on any failure; trust does not
        advance past a failed height."""
        if fc.height != self.next_height:
            raise CertificationError(
                f"continuous certify expects height {self.next_height}, "
                f"got {fc.height}")
        if fc.validators.hash() == self.validators.hash():
            StaticCertifier(self.chain_id, self.validators,
                            self.verifier).certify(fc)
            self.static_certified += 1
        else:
            # (1) +2/3 of the signing set, (2) trusted-set endorsement
            StaticCertifier(self.chain_id, fc.validators,
                            self.verifier).certify(fc)
            sh = fc.signed_header
            try:
                _trusted_set_endorsement(self.validators, self.chain_id,
                                         sh.block_id, sh.height,
                                         sh.commit,
                                         verifier=self.verifier)
            except ValueError as e:
                raise CertificationError(
                    f"valset transition at height {fc.height}: "
                    f"{e}") from e
            self.validators = fc.validators
            self.updates += 1
        self.app_hashes[fc.height] = fc.signed_header.header.app_hash
        while len(self.app_hashes) > 16:
            self.app_hashes.pop(next(iter(self.app_hashes)))
        self.next_height += 1


def default_window(n_vals: int) -> int:
    """Headers per pooled dispatch window: ~32k signatures in flight
    for a set of up to 512 validators (dispatches amortized, chunks
    fetched in parallel, memory bounded; the figure comes from sweeps
    on an earlier host and is not re-measured on the attached chip),
    and never under 64 headers: a larger set gets 64 * n_vals
    signatures a window, 640,000 at 10,000 validators (PERF.md
    section 7 has a probe at that size).
    Exposed so benches can warm the exact tail batch shape a partial
    chain will dispatch."""
    return max(64, 32768 // max(1, n_vals))


def certify_chain(chain_id: str, fcs: List[FullCommit],
                  trusted: Optional[ValidatorSet] = None,
                  verifier=None, window: Optional[int] = None) -> None:
    """Certify consecutive FullCommits with pooled, PIPELINED signature
    batches — the 1M-header lite-chain workload (BASELINE.json config 5)
    instead of per-header VerifyCommit loops (lite/performance_test.go's
    shape).

    Structural checks + valset-continuity run on host per header; the
    signatures of `window` headers at a time go to the device in one
    BatchVerifier dispatch. Like fast-sync's window engine, the dispatch
    of window k resolves on a helper thread (the blocking fetch
    releases the GIL) while the host collects window k+1. Memory stays
    bounded at ~window·V items.

    `trusted`: valset required to have signed fcs[0] (defaults to
    fcs[0].validators — self-certifying chain head). Raises
    CertificationError on the first bad header."""
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.telemetry import trace
    verifier = verifier or default_verifier()
    if not fcs:
        return
    expect_vals = trusted or fcs[0].validators
    if window is None:
        window = default_window(len(expect_vals))

    def collect(window_fcs, req):
        # two passes over the window, each timed once: the headers
        # (lite.headers), then the commits' signatures as columns
        # (lite.votes). The first bad header in chain order is the one
        # reported: the votes stop where the headers did.
        t0 = time.perf_counter()
        bad = None
        for k, fc in enumerate(window_fcs):
            try:
                fc.validate_basic(chain_id)
                if fc.validators.hash() != expect_vals.hash():
                    raise ValidatorsChangedError(
                        f"valset discontinuity at height {fc.height}")
            except CertificationError as e:
                bad, window_fcs = e, window_fcs[:k]
                break
        t1 = time.perf_counter()
        trace.complete("lite.headers", t0, t1, req=req)
        batches = []
        spans = []  # (item_power, lo, n, height)
        lo = 0
        for fc in window_fcs:
            sh = fc.signed_header
            try:
                items, item_power = expect_vals.commit_verification_items(
                    chain_id, sh.block_id, sh.height, sh.commit)
            except ValueError as e:
                raise CertificationError(
                    f"height {fc.height}: {e}") from e
            spans.append((item_power, lo, len(items), fc.height))
            lo += len(items)
            batches.append(items)
            # constant-valset segments only: when the set changes, the
            # caller splits the chain there and bridges with
            # DynamicCertifier.update (that transition needs
            # verify_commit_any, which can't pool across the boundary)
        if bad is not None:
            raise bad
        items_w = SigColumns.concat(batches)
        trace.complete("lite.votes", t1, time.perf_counter(), req=req)
        return items_w, spans

    def check(spans, ok):
        for item_power, lo, n, height in spans:
            try:
                expect_vals.check_commit_results(ok[lo:lo + n], item_power)
            except ValueError as e:
                raise CertificationError(f"height {height}: {e}") from e

    def settle(spans, fut, req):
        with trace.span("lite.wait", req=req):
            ok = fut.result()
        with trace.span("lite.check", req=req):
            check(spans, ok)

    pool = ThreadPoolExecutor(max_workers=1,
                              thread_name_prefix="tm-lite-resolve")
    try:
        pending = None  # (spans, future, the window's first height)
        for lo in range(0, len(fcs), window):
            req = fcs[lo].height
            with trace.span("lite.collect", req=req):
                items_w, spans = collect(fcs[lo:lo + window], req)
            fut = pool.submit(verifier.verify_async(items_w))
            if pending is not None:
                settle(*pending)
            pending = (spans, fut, req)
        if pending is not None:
            settle(*pending)
    finally:
        pool.shutdown(wait=False)
