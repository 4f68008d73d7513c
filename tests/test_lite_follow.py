"""ContinuousCertifier.advance_many: a run of consecutive FullCommits
across validator-set changes on the pooled window engine, held to the
rule as it was written before the engine took it over (`header_by_
header` below transcribes the former `advance` and its synchronous
`_trusted_set_endorsement`, which verified the endorsing signatures a
second time) and to the plain reference, benchmark/literef.py.

The chain: 8 validators of unequal stake, 48 headers, 12 stake changes
and 4 membership changes at seeded heights, signed on the host; windows
of 5 and 8 headers, so boundaries fall at a window's first, last and
inner headers."""

import copy
import threading

import pytest

from benchmark import literef
from benchmark.churnchain import MEMBERSHIP, STAKE, ChurnChain
from benchmark.drivers.follow import kind_of
from tendermint_tpu import telemetry
from tendermint_tpu.lite import (CertificationError, ContinuousCertifier,
                                 StaticCertifier, certify_chain)
from tendermint_tpu.lite.certifier import default_window
from tendermint_tpu.models.verifier import default_verifier
from tendermint_tpu.types.vote import VoteType

N_HEADERS, N_VALS = 48, 8
WINDOWS = (5, 8)


@pytest.fixture(scope="module")
def chain():
    return ChurnChain(11, N_HEADERS, N_VALS, stake_changes=12,
                      membership_changes=4, sign="host")


# ------------------------------------------- the rule, header by header

def _endorsed(trusted, chain_id, block_id, commit):
    """The former _trusted_set_endorsement, line for line."""
    items, powers, seen = [], [], set()
    for pc in commit.precommits:
        if pc is None or pc.block_id != block_id:
            continue
        oi, ov = trusted.get_by_address(pc.validator_address)
        if ov is None or oi in seen:
            continue
        seen.add(oi)
        items.append((ov.pubkey, pc.sign_bytes(chain_id), pc.signature))
        powers.append(ov.voting_power)
    old_power = 0
    for valid, power in zip(default_verifier().verify(items), powers):
        if not valid:
            raise ValueError("invalid signature in commit")
        old_power += power
    total = trusted.total_voting_power()
    if not old_power * 3 > total:
        raise ValueError(
            f"insufficient trusted-set endorsement: got {old_power}")


class HeaderByHeader:
    """The former ContinuousCertifier.advance on a state of its own."""

    def __init__(self, chain_id, trusted, next_height=1):
        self.chain_id, self.validators = chain_id, trusted
        self.next_height = next_height
        self.static_certified = self.updates = 0
        self.app_hashes = {}

    def advance(self, fc):
        if fc.height != self.next_height:
            raise CertificationError(
                f"continuous certify expects height {self.next_height}")
        if fc.validators.hash() == self.validators.hash():
            StaticCertifier(self.chain_id, self.validators).certify(fc)
            self.static_certified += 1
        else:
            StaticCertifier(self.chain_id, fc.validators).certify(fc)
            sh = fc.signed_header
            try:
                _endorsed(self.validators, self.chain_id, sh.block_id,
                          sh.commit)
            except ValueError as e:
                raise CertificationError(
                    f"valset transition at height {fc.height}: {e}") from e
            self.validators = fc.validators
            self.updates += 1
        self.app_hashes[fc.height] = fc.signed_header.header.app_hash
        while len(self.app_hashes) > 16:
            self.app_hashes.pop(next(iter(self.app_hashes)))
        self.next_height += 1


def state(cert):
    return (cert.next_height - 1, cert.validators.hash(), cert.updates,
            cert.static_certified, dict(cert.app_hashes))


def by_the_rule(chain, trusted, fcs):
    """(state, failing height, kind) of the rule applied one header at
    a time; height and kind None where every header passes."""
    ref = HeaderByHeader(chain.chain_id, trusted)
    for fc in fcs:
        try:
            ref.advance(fc)
        except CertificationError as e:
            return state(ref), fc.height, kind_of(e)
    return state(ref), None, None


def by_the_engine(chain, trusted, fcs, window):
    cert = ContinuousCertifier(chain.chain_id, trusted)
    try:
        cert.advance_many(fcs, window=window)
    except CertificationError as e:
        return state(cert), e.height, kind_of(e)
    return state(cert), None, None


def by_the_reference(chain, wire, valsets_wire, set_of):
    sets = [literef.parse_validators(w) for w in valsets_wire]
    out = literef.follow(chain.chain_id, sets[0], [
        literef.parse_full_commit(w, sets[set_of[i]])
        for i, w in enumerate(wire)])
    return out


# ------------------------------------------------------- a genuine chain

def test_the_chain_is_the_one_asked_for(chain):
    kinds = list(chain.change_at.values())
    assert kinds.count(STAKE) == 12 and kinds.count(MEMBERSHIP) == 4
    assert 1 not in chain.change_at and len(chain.valsets_wire) == 17
    trusted, fcs = chain.decode()
    assert all(len(fc.validators) == N_VALS for fc in fcs)
    assert len({v.voting_power for v in trusted.validators}) == N_VALS
    assert len(chain.seed_of) == N_VALS + 4 and chain.n_sigs == 48 * 8
    assert len(set(chain.msgs)) == chain.n_sigs   # a timestamp a vote
    # a boundary at a window's first, last and inner header
    for w in WINDOWS:
        at = {(h - 1) % w for h in chain.change_at}
        assert 0 in at and w - 1 in at and at - {0, w - 1}
    # unchanged headers share their predecessor's set object
    assert len({id(fc.validators) for fc in fcs}) == 17


@pytest.mark.parametrize("window", WINDOWS + (16,))
def test_a_genuine_chain_is_followed_to_its_end(chain, window):
    trusted, fcs = chain.decode()
    want, height, _kind = by_the_rule(chain, trusted, fcs)
    assert height is None and want[0] == N_HEADERS and want[2] == 16
    assert by_the_engine(chain, trusted, fcs, window) == (want, None, None)
    out = by_the_reference(chain, chain.wire, chain.valsets_wire,
                           chain.set_of)
    assert (out.height, out.changes, out.refused_at) == (48, 16, None)
    assert literef.validators_hash(out.trusted) == want[1]


@pytest.mark.parametrize("window", WINDOWS)
def test_the_state_after_every_prefix(chain, window):
    trusted, fcs = chain.decode()
    ref = HeaderByHeader(chain.chain_id, trusted)
    for n, fc in enumerate(fcs):
        got, height, _ = by_the_engine(chain, trusted, fcs[:n], window)
        assert height is None and got == state(ref)
        ref.advance(fc)


def test_a_run_is_taken_up_where_the_last_one_ended(chain):
    trusted, fcs = chain.decode()
    cert = ContinuousCertifier(chain.chain_id, trusted)
    for lo, hi in ((0, 7), (7, 8), (8, 8), (8, 30), (30, 48)):
        cert.advance_many(fcs[lo:hi], window=5)
    assert state(cert) == by_the_rule(chain, trusted, fcs)[0]


def test_advance_is_a_run_of_one(chain):
    trusted, fcs = chain.decode()
    cert = ContinuousCertifier(chain.chain_id, trusted)
    ref = HeaderByHeader(chain.chain_id, trusted)
    before = {t.name for t in threading.enumerate()}
    for fc in fcs:
        cert.advance(fc)
        ref.advance(fc)
        assert state(cert) == state(ref)
    # nothing to overlap with: no helper thread was started
    assert {t.name for t in threading.enumerate()} <= before


# ------------------------------------------------------------- refusals

def _slot_of_largest(fc):
    vals = fc.validators.validators
    return max(range(len(vals)), key=lambda i: vals[i].voting_power)


def wrong_chain_id(chain, fcs, i):
    fcs[i].signed_header.header.chain_id = "another-chain"


def wrong_validators(chain, fcs, i):
    other = next(fc.validators for fc in fcs
                 if fc.validators.hash() != fcs[i].validators.hash())
    fcs[i].validators = other


def header_not_the_commits(chain, fcs, i):
    fcs[i].signed_header.header.app_hash = b"\x99" * 32


def flipped_signature(chain, fcs, i):
    vote = fcs[i].signed_header.commit.precommits[_slot_of_largest(fcs[i])]
    sig = vote.signature
    vote.signature = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]


def no_quorum(chain, fcs, i):
    """The three largest stakes stay away: under 2/3 is left."""
    fc = fcs[i]
    pcs = fc.signed_header.commit.precommits
    vals = fc.validators.validators
    for slot in sorted(range(len(vals)),
                       key=lambda k: -vals[k].voting_power)[:3]:
        pcs[slot] = None


def not_a_precommit(chain, fcs, i):
    fcs[i].signed_header.commit.precommits[2].type = VoteType.PREVOTE


def a_height_left_out(chain, fcs, i):
    del fcs[i]


def hostile_set(chain, fcs, i):
    """A set of fresh keys signs its own header with its full quorum."""
    wire, sets, set_of = chain.hostile_transition(i + 1, seed=5)
    _trusted, forged = chain.decode(wire, sets, set_of)
    fcs[i] = forged[-1]


def address_of_another(chain, fcs, i):
    """A vote that claims the address of another validator, one the
    set before knows too: its signature is then held to that
    validator's key by the trusted set, and to its own slot's key by
    the signing set."""
    pcs = fcs[i].signed_header.commit.precommits
    pcs[0].validator_address = next(
        pc.validator_address for pc in pcs[1:]
        if fcs[i - 1].validators.has_address(pc.validator_address))


FAULTS = {f.__name__: f for f in (
    wrong_chain_id, wrong_validators, header_not_the_commits,
    flipped_signature, no_quorum, not_a_precommit, a_height_left_out,
    hostile_set, address_of_another)}
WANT = {"wrong_chain_id": literef.CHAIN_ID,
        "wrong_validators": literef.VALIDATORS_HASH,
        "header_not_the_commits": literef.HEADER_HASH,
        "flipped_signature": literef.SIGNATURE,
        "no_quorum": literef.QUORUM,
        "not_a_precommit": literef.COMMIT,
        "a_height_left_out": literef.HEIGHT,
        "hostile_set": literef.ENDORSEMENT}


def faulted(chain, *faults):
    """The decoded chain with (fault, height) applied, each to objects
    of its own."""
    trusted, fcs = chain.decode()
    fcs = [copy.deepcopy(fc) if any(fc.height == h for _f, h in faults)
           else fc for fc in fcs]
    for name, height in sorted(faults, key=lambda f: -f[1]):
        FAULTS[name](chain, fcs, height - 1)
    return trusted, fcs


def a_boundary(chain, window, at):
    """A change height that is a window's first (`at` 0), last (-1) or
    an inner (1) header, away from the chain's ends."""
    for h in sorted(chain.change_at):
        k = (h - 1) % window
        if 3 < h < N_HEADERS - 2 and (
                k == 0 if at == 0 else k == window - 1 if at == -1
                else 0 < k < window - 1):
            return h
    raise AssertionError("no such boundary in this chain")


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("where", (-1, 0, 1), ids=("before", "at", "after"))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_refusal_is_the_rules_at_its_height(chain, fault, where, window):
    height = a_boundary(chain, window, at=where) + where
    trusted, fcs = faulted(chain, (fault, height))
    want = by_the_rule(chain, trusted, fcs)
    got = by_the_engine(chain, trusted, fcs, window)
    assert got == want
    if fault == "address_of_another":
        # at a boundary the trusted set holds the signature to another
        # key and refuses it; elsewhere nobody reads the address
        moved = height in chain.change_at
        assert want[1:] == ((height, literef.ENDORSEMENT_SIGNATURE)
                            if moved else (None, None))
    else:
        # with a height left out it is the header after the gap that is
        # refused; trust stands one below the fault either way
        refused = height + 1 if fault == "a_height_left_out" else height
        assert want[1:] == (refused, WANT[fault])
        assert want[0][0] == height - 1


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("late", ("wrong_chain_id", "not_a_precommit",
                                  "a_height_left_out", "hostile_set"))
@pytest.mark.parametrize("early", (None, "flipped_signature", "no_quorum"))
def test_a_later_windows_fault_waits_for_the_window_in_flight(
        chain, early, late, window):
    """A fault the collect of window k+1 finds while window k's verdicts
    are out: the earlier window is judged first, and what it certifies
    is trusted before the later fault is raised."""
    at_late = 2 * window + 2
    faults = [(late, at_late)]
    if early:
        faults.append((early, window + 1))
    trusted, fcs = faulted(chain, *faults)
    want = by_the_rule(chain, trusted, fcs)
    if early is None:       # the header after a gap is the one refused
        assert want[1] == at_late + (late == "a_height_left_out")
        assert want[0][0] == at_late - 1
    else:
        assert want[1] == window + 1 and want[0][0] == window
    assert by_the_engine(chain, trusted, fcs, window) == want


@pytest.mark.parametrize("window", WINDOWS)
def test_an_unknown_claimed_address_counts_for_nothing(chain, window):
    """A vote at a boundary that claims an address the trusted set does
    not know is skipped by the endorsement, as it was."""
    height = a_boundary(chain, window, at=1)
    trusted, fcs = faulted(chain)
    fc = fcs[height - 1] = copy.deepcopy(fcs[height - 1])
    fc.signed_header.commit.precommits[0].validator_address = b"\x07" * 20
    want = by_the_rule(chain, trusted, fcs)
    assert want[1] is None
    assert by_the_engine(chain, trusted, fcs, window) == want


# ------------------------------------------- against the plain reference

TAMPERED = {
    "flipped_signature": (lambda c, h: c.flipped_signature(h, 3),
                          literef.SIGNATURE),
    "forged_header": (lambda c, h: c.forged_header(h), literef.SIGNATURE),
    "wrong_validators": (lambda c, h: c.wrong_validators(h),
                         literef.VALIDATORS_HASH),
    "hostile_transition": (lambda c, h: c.hostile_transition(h, seed=9),
                           literef.ENDORSEMENT),
}


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("tamper", sorted(TAMPERED))
def test_a_tampered_chain_is_refused_as_the_reference_refuses_it(
        chain, tamper, window):
    make, kind = TAMPERED[tamper]
    height = a_boundary(chain, window, at=1)
    wire, sets, set_of = make(chain, height)
    trusted, fcs = chain.decode(wire, sets, set_of)
    got = by_the_engine(chain, trusted, fcs, window)
    out = by_the_reference(chain, wire, sets, set_of)
    assert got[1:] == (height, kind) == (out.refused_at, out.kind)
    assert got[0][0] == out.height == height - 1
    assert got[0][1] == literef.validators_hash(out.trusted)


# -------------------------------------------- the constant set unchanged

def test_certify_chain_still_refuses_a_set_that_moves(chain):
    trusted, fcs = chain.decode()
    first = min(chain.change_at)
    certify_chain(chain.chain_id, fcs[:first - 1], trusted=trusted, window=5)
    with pytest.raises(CertificationError, match="discontinuity") as e:
        certify_chain(chain.chain_id, fcs, trusted=trusted, window=5)
    assert e.value.height == first


def test_the_default_window_is_the_trusted_sets(chain):
    assert default_window(N_VALS) == 4096 and default_window(100) == 327


# ------------------------------------------------- spans and counters

def test_windows_transitions_and_spans_are_recorded(chain):
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        telemetry.TRACER.clear()
        counts = {k: telemetry.value("lite_transitions_total", {"kind": k})
                  or 0 for k in (STAKE, MEMBERSHIP)}
        windows = telemetry.value("lite_windows_total") or 0
        trusted, fcs = chain.decode()
        ContinuousCertifier(chain.chain_id, trusted).advance_many(
            fcs, window=5)
        assert telemetry.value("lite_windows_total") - windows == 10
        for k, n in ((STAKE, 12), (MEMBERSHIP, 4)):
            assert telemetry.value("lite_transitions_total",
                                   {"kind": k}) - counts[k] == n
        events = telemetry.TRACER.events()
        by_id = {e["id"]: e for e in events}
        named = {n: [e for e in events if e["name"] == n]
                 for n in ("lite.sethash", "lite.transition",
                           "lite.collect", "lite.check")}
        assert all(len(v) == 10 for v in named.values())
        for child, parent in (("lite.sethash", "lite.collect"),
                              ("lite.transition", "lite.check")):
            for e in named[child]:
                assert by_id[e["parent"]]["name"] == parent
                assert e["req"] == by_id[e["parent"]]["req"]
        assert sum(e["args"]["sets"] for e in named["lite.sethash"]) >= 17
    finally:
        telemetry.set_enabled(was)
