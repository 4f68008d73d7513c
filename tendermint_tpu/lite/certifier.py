"""Certifiers — the light-client trust ladder (lite/).

StaticCertifier   — fixed valset, certify one height
                    (lite/static_certifier.go:22,57)
DynamicCertifier  — follows valset changes via verify_commit_any
                    (lite/dynamic_certifier.go:20,70)
InquiringCertifier— auto-updates through a Provider with BISECTION over
                    heights when the valset moved too far at once
                    (lite/inquiring_certifier.go:15,67,137-163)

ContinuousCertifier— follows a CHURNING valset height by height, never
                    skipping one: `advance_many` certifies a run of
                    consecutive FullCommits across every valset delta
                    (the adjacent-height rule), `advance` one of them.

certify_chain     — a whole run of consecutive FullCommits of ONE
                    constant valset (BASELINE.json config 5's workload).

certify_chain and ContinuousCertifier.advance_many are the TPU batch
path, and one engine (_certify_windows): the signatures of a window of
headers, whichever sets signed them, go to the verifier as one pooled
batch, and window k resolves on a helper thread while window k+1 is
collected.
"""

from __future__ import annotations

import time
from typing import List, Optional

from tendermint_tpu import telemetry
from tendermint_tpu.lite.types import (
    CertificationError,
    FullCommit,
    SignedHeader,
    ValidatorsChangedError,
)
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.validator_set import ValidatorSet

_m_windows = telemetry.counter(
    "lite_windows_total",
    "Windows of headers whose signatures the light client's batch path "
    "(certify_chain, ContinuousCertifier.advance_many) handed the "
    "verifier as one pooled batch", ())
_m_transitions = telemetry.counter(
    "lite_transitions_total",
    "Changes of validator set a ContinuousCertifier crossed under the "
    "adjacent-height rule, by what moved: membership (a key joined or "
    "left) or stake (the same keys, other powers)", ("kind",))


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


class ContinuousCertifier:
    """Certify EVERY height of a chain whose valset churns, in order.

    Per height: same valset hash as trusted -> plain certify. Changed
    hash -> the adjacent-height transition rule: (1) the commit must
    carry +2/3 of the NEW (signing) set, every signer counted, as
    verify_commit has it; (2) the TRUSTED set must endorse it with
    STRICTLY more than 1/3 of its own power among the signers it knows
    (ValidatorSet.endorsement / check_endorsement: the later-Tendermint
    light-client trust level, sound because <1/3 byzantine means at
    least one honest trusted validator signed the new set into power).
    The v0.16 VerifyCommitAny overlap rule (DynamicCertifier.update)
    remains the JUMP bridge: it counts only overlap validators toward
    the new set, which rejects honest quorum-sparse commits the moment
    one validator joins or leaves.

    It NEVER skips a height — feeding a non-consecutive height raises;
    bridging a gap is DynamicCertifier.update / InquiringCertifier
    bisection territory, whose strict v0.16 rule refuses any jump that
    moved more than 1/3 of the trusted power (test-pinned). `trusted`
    is the valset expected to sign `next_height` (genesis set for
    next_height=1)."""

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
        self.advance_many([fc])

    def advance_many(self, fcs: List[FullCommit],
                     window: Optional[int] = None) -> None:
        """Certify `fcs`, the next heights in order, through every
        change of set among them, `window` headers to a pooled batch
        (default_window of the trusted set's size). Raises
        CertificationError, with the failing `height`, at the FIRST bad
        header in chain order; trust then stands at the height before
        it, as after as many calls of `advance`."""
        if not fcs:
            return
        if window is None:
            window = default_window(len(self.validators))
        _certify_windows(self.chain_id, fcs, self.validators,
                         self.verifier, window, follower=self)

    def _trust(self, fcs: List[FullCommit], moved: dict) -> None:
        """Advance trust over `fcs`, in chain order: what the engine
        hands over once their verdicts are in and judged. `moved` maps
        the index of a header that named another set than the one
        trusted before it to that set, in ascending order (it may run
        past `fcs`, where a later header failed)."""
        n_moved = 0
        for k, before in moved.items():
            if k >= len(fcs):
                break
            n_moved += 1
            self.validators = signing = fcs[k].validators
            _m_transitions.labels(
                "stake" if before._index.keys() == signing._index.keys()
                else "membership").inc()
        self.updates += n_moved
        self.static_certified += len(fcs) - n_moved
        for fc in fcs[-16:]:
            self.app_hashes[fc.height] = fc.signed_header.header.app_hash
        while len(self.app_hashes) > 16:
            self.app_hashes.pop(next(iter(self.app_hashes)))
        self.next_height += len(fcs)


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
    """Certify consecutive FullCommits of ONE constant valset with
    pooled, PIPELINED signature batches — the 1M-header lite-chain
    workload (BASELINE.json config 5) instead of per-header VerifyCommit
    loops (lite/performance_test.go's shape). A header that names
    another set raises ValidatorsChangedError: a chain whose set moves
    is ContinuousCertifier.advance_many's, on the same engine.

    `trusted`: valset required to have signed fcs[0] (defaults to
    fcs[0].validators — self-certifying chain head). Raises
    CertificationError on the first bad header."""
    if not fcs:
        return
    trusted = trusted or fcs[0].validators
    if window is None:
        window = default_window(len(trusted))
    _certify_windows(chain_id, fcs, trusted, verifier, window)


def _certify_windows(chain_id: str, fcs: List[FullCommit],
                     trusted: ValidatorSet, verifier, window: int,
                     follower: Optional[ContinuousCertifier] = None) -> None:
    """The window engine of the light client's batch path.

    Structural checks run on host per header; the signatures of `window`
    headers at a time go to the device in one BatchVerifier dispatch.
    Like fast-sync's window engine, the dispatch of window k resolves on
    a helper thread (the blocking fetch releases the GIL) while the host
    collects window k+1. Memory stays bounded at ~window·V items.

    With no `follower` every header must name `trusted`. With one (its
    `validators` are `trusted`), heights must run on from its
    next_height, a header that names another set is a boundary inside
    the window like any other header: its commit is collected under the
    set it carries, and judged by the adjacent-height rule when the
    verdicts are in. Window k+1 is collected under the sets window k's
    headers carried, before those are trusted; the follower's trust
    moves only in `check`, header by header up to the first that fails.
    The first bad header in chain order is the one raised, whichever
    window found it first."""
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.telemetry import trace
    verifier = verifier or default_verifier()
    expect = trusted        # what the next header collected is held to
    start = follower.next_height if follower is not None else None

    def failed(fc, why, cause=None) -> CertificationError:
        e = why if isinstance(why, CertificationError) \
            else CertificationError(why)
        e.height, e.__cause__ = fc.height, cause
        return e

    def collect(window_fcs, req, base):
        # two passes over the window, each timed once: the headers
        # (lite.headers; the sets they hand over are hashed first, and
        # that part is lite.sethash), then the commits' signatures as
        # columns (lite.votes). Returns the batch and spans of the
        # headers before the first bad one, the set the window's first
        # header is held to, `moved` (index in the window -> the set
        # trusted before that header, for each that named another; a
        # constant set allocates nothing a header for it) and the bad
        # header's error: the votes stop where the headers did.
        nonlocal expect
        t0 = time.perf_counter()
        seen = None
        n_sets = 0
        for fc in window_fcs:
            if fc.validators is not seen:
                seen = fc.validators
                seen.hash()
                n_sets += 1
        t1 = time.perf_counter()
        trace.complete("lite.sethash", t0, t1, req=req, sets=n_sets)
        bad = None
        first, moved = expect, {}
        for k, fc in enumerate(window_fcs):
            try:
                if follower is not None and fc.height != start + base + k:
                    raise CertificationError(
                        f"continuous certify expects height "
                        f"{start + base + k}, got {fc.height}")
                fc.validate_basic(chain_id)
                vs = fc.validators
                if vs is not expect and vs.hash() != expect.hash():
                    if follower is None:
                        raise ValidatorsChangedError(
                            f"valset discontinuity at height {fc.height}")
                    moved[k], expect = expect, vs
            except CertificationError as e:
                bad, window_fcs = failed(fc, e), window_fcs[:k]
                break
        t2 = time.perf_counter()
        trace.complete("lite.headers", t0, t2, req=req)
        batches = []
        spans = []  # (item_power, lo, n, fc)
        lo = 0
        signing = first
        for k, fc in enumerate(window_fcs):
            if moved and k in moved:
                signing = fc.validators
            sh = fc.signed_header
            try:
                items, item_power = signing.commit_verification_items(
                    chain_id, sh.block_id, sh.height, sh.commit)
            except ValueError as e:
                bad = failed(fc, f"height {fc.height}: {e}", e)
                break
            spans.append((item_power, lo, len(items), fc))
            lo += len(items)
            batches.append(items)
        items_w = SigColumns.concat(batches)
        trace.complete("lite.votes", t2, time.perf_counter(), req=req)
        return items_w, (spans, first, moved), bad

    def check(spans, first, moved, ok, req):
        # two passes again: every header's quorum under its signing
        # set, then (a follower's) boundaries before the first failure
        # under the set trusted before each, and the switch of trust
        bad = None
        signing = first
        for k, (item_power, lo, n, fc) in enumerate(spans):
            if moved and k in moved:
                signing = fc.validators
            try:
                signing.check_commit_results(ok[lo:lo + n], item_power)
            except ValueError as e:
                bad, spans = failed(fc, f"height {fc.height}: {e}", e), \
                    spans[:k]
                break
        if follower is not None:
            with trace.span("lite.transition", req=req):
                for k, before in moved.items():
                    if k >= len(spans):
                        break
                    fc = spans[k][3]
                    sh = fc.signed_header
                    try:
                        power, extra = before.endorsement(
                            fc.validators, chain_id, sh.block_id, sh.commit)
                        before.check_endorsement(
                            power, verifier.verify(extra) if extra else ())
                    except ValueError as e:
                        bad, spans = failed(
                            fc, f"valset transition at height "
                                f"{fc.height}: {e}", e), spans[:k]
                        break
                follower._trust([s[3] for s in spans], moved)
        if bad is not None:
            raise bad

    def settle(judged, resolve, req):
        with trace.span("lite.wait", req=req):
            ok = resolve()
        with trace.span("lite.check", req=req):
            check(*judged, ok, req)

    # a run of one window has nothing to overlap: it resolves on the
    # caller's thread (ContinuousCertifier.advance, a header at a time)
    pool = ThreadPoolExecutor(max_workers=1,
                              thread_name_prefix="tm-lite-resolve") \
        if len(fcs) > window else None
    try:
        pending = None  # (what check judges, resolver, first height)
        for lo in range(0, len(fcs), window):
            req = fcs[lo].height
            with trace.span("lite.collect", req=req):
                items_w, judged, bad = collect(fcs[lo:lo + window], req, lo)
            resolve = verifier.verify_async(items_w)
            _m_windows.inc()
            if pool is not None:
                resolve = pool.submit(resolve).result
            if pending is not None:
                settle(*pending)
            pending = (judged, resolve, req)
            if bad is not None:
                # the headers before it first: theirs is the earlier
                # failure, if they have one
                settle(*pending)
                raise bad
        settle(*pending)
    finally:
        if pool is not None:
            pool.shutdown(wait=False)
