"""benchmark/literef.py, the plain reference of a light client that
follows a moving validator set, against cases built by hand with
OpenSSL: both tallies at their thresholds, who counts toward the
endorsement, every kind of refusal, and its two hashes against the
program's."""

import pytest

from benchmark import commitref, literef
from benchmark.commitref import PRECOMMIT, PlainVote
from benchmark.kvref import openssl_signer

CHAIN = "literef-test"
PARTS = (1, b"\x22" * 32)


class Val:
    def __init__(self, i: int, power: int):
        self.key = openssl_signer(bytes([i + 1]) * 32)
        self.pub = self.key.public_key().public_bytes_raw()
        self.addr = literef.address_of(self.pub)
        self.power = power


def vals(*powers, base=0):
    return [Val(base + i, p) for i, p in enumerate(powers)]


def plain(members):
    """(the set as the reference takes it, its members in that order)."""
    members = sorted(members, key=lambda v: v.addr)
    return [(v.pub, v.power) for v in members], members


def full_commit(height, members, absent=(), other_block=(), claims=None,
                chain_id=CHAIN, app=b"\x01"):
    """`members` all sign `height`, but for `absent`; `other_block`
    sign another block; `claims` maps a member to the address its vote
    claims."""
    validators, members = plain(members)
    header = {"chain_id": chain_id, "height": height,
              "validators_hash": literef.validators_hash(validators).hex(),
              "app_hash": (app * 32).hex()}
    block_id = (literef.header_hash(header),) + PARTS
    commit, addresses = [], []
    for j, v in enumerate(members):
        if v in absent:
            commit.append(None)
            addresses.append(None)
            continue
        bid = (b"\x55" * 32,) + PARTS if v in other_block else block_id
        vote = PlainVote(height, 0, PRECOMMIT, height * 10 ** 9 + j, bid, b"")
        commit.append(vote._replace(
            signature=v.key.sign(commitref.sign_bytes(chain_id, vote))))
        addresses.append((claims or {}).get(v, v.addr))
    return literef.PlainFullCommit(header, block_id, commit, addresses,
                                   validators)


def follow(trusted_members, fcs, **kw):
    return literef.follow(CHAIN, plain(trusted_members)[0], fcs, **kw)


# ----------------------------------------------------------- both tallies

@pytest.mark.parametrize("absent,accepted", [
    ((2, 3), False),        # 40 of 60 is exactly 2/3: refused
    ((2,), True),           # 41: one unit above
    ((), True),
    ((0, 2, 3), False),
])
def test_the_signing_sets_quorum_is_strictly_over_two_thirds(
        absent, accepted):
    members = vals(20, 20, 19, 1)
    out = follow(members, [full_commit(
        1, members, absent=[members[i] for i in absent])])
    assert (out.height == 1) is accepted
    if not accepted:
        assert (out.refused_at, out.kind, out.height) == (
            1, literef.QUORUM, 0)


@pytest.mark.parametrize("old_power,accepted", [
    (10, False),        # exactly 1/3 of the trusted 30: refused
    (11, True),         # one unit above
    (9, False),
])
def test_the_trusted_sets_endorsement_is_strictly_over_one_third(
        old_power, accepted):
    """One trusted validator stays in the new set; the rest of the new
    set is fresh keys, so the new set's own quorum never lacks."""
    stays = Val(0, old_power)
    trusted = [stays] + vals(30 - old_power, base=1)
    new = [Val(0, 1)] + vals(50, 50, base=10)
    out = follow(trusted, [full_commit(1, new)])
    assert (out.height, out.changes) == ((1, 1) if accepted else (0, 0))
    if accepted:
        assert out.trusted == plain(new)[0]
    else:
        assert (out.refused_at, out.kind) == (1, literef.ENDORSEMENT)
        assert out.trusted == plain(trusted)[0]


def test_the_endorsement_weighs_by_the_trusted_sets_powers():
    """The validator that stays holds 1 of the new set's 101 and 20 of
    the trusted set's 30: it endorses alone."""
    trusted = [Val(0, 20)] + vals(10, base=1)
    new = [Val(0, 1)] + vals(50, 50, base=10)
    assert follow(trusted, [full_commit(1, new)]).changes == 1


def test_a_vote_for_another_block_endorses_nothing():
    trusted = [Val(0, 20)] + vals(10, base=1)
    new = [Val(0, 1)] + vals(50, 50, base=10)
    fc = full_commit(1, new, other_block=[new[0]])
    out = follow(trusted, [fc])
    assert (out.refused_at, out.kind) == (1, literef.ENDORSEMENT)


def test_an_address_claimed_twice_is_counted_once():
    """Two votes claim the one trusted address that is known: its 10 of
    30 count once, and once is not over a third."""
    stays = Val(0, 10)
    trusted = [stays] + vals(20, base=1)
    new = [Val(0, 1)] + vals(50, 50, base=10)
    fc = full_commit(1, new, claims={new[1]: stays.addr})
    out = follow(trusted, [fc])
    # the second claim is never verified (counted already) nor counted
    assert (out.refused_at, out.kind) == (1, literef.ENDORSEMENT)


def test_a_vote_under_anothers_address_is_held_to_that_key():
    trusted = vals(10, 10, 10)
    new = trusted[:2] + vals(10, base=10)
    fc = full_commit(1, new, claims={new[2]: trusted[2].addr})
    out = follow(trusted, [fc])
    assert (out.refused_at, out.kind) == (
        1, literef.ENDORSEMENT_SIGNATURE)


def test_an_unknown_address_is_skipped():
    trusted = vals(10, 10, 10)
    new = trusted[:2] + vals(10, base=10)
    fc = full_commit(1, new, claims={new[2]: b"\x07" * 20})
    assert follow(trusted, [fc]).changes == 1


# ------------------------------------------------------- the other kinds

def test_a_run_across_changes_and_where_it_stops():
    a = vals(10, 10, 10, 10)
    b = a[:3] + vals(10, base=10)               # one leaves, one joins
    c = [Val(0, 12)] + b[1:]                    # a stake moves
    fcs = [full_commit(1, a), full_commit(2, a), full_commit(3, b),
           full_commit(4, b), full_commit(5, c)]
    out = follow(a, fcs)
    assert (out.height, out.changes, out.refused_at) == (5, 2, None)
    assert out.trusted == plain(c)[0]
    out = follow(a, fcs[:2] + fcs[3:])
    assert (out.height, out.refused_at, out.kind) == (2, 3, literef.HEIGHT)
    out = follow(a, fcs[1:], next_height=2)
    assert (out.height, out.changes) == (5, 2)


@pytest.mark.parametrize("kind", [
    literef.CHAIN_ID, literef.VALIDATORS_HASH, literef.HEADER_HASH,
    literef.SIGNATURE, literef.COMMIT])
def test_a_fault_is_refused_at_its_height_for_its_kind(kind):
    a = vals(10, 10, 10, 10)
    good = full_commit(1, a)
    fc = full_commit(2, a)
    if kind == literef.CHAIN_ID:
        fc = full_commit(2, a, chain_id="another")
    elif kind == literef.VALIDATORS_HASH:
        fc = fc._replace(validators=plain(vals(10, 10, 10, 11))[0])
    elif kind == literef.HEADER_HASH:
        fc = fc._replace(header=dict(fc.header, app_hash="ff" * 32))
    elif kind == literef.SIGNATURE:
        sig = fc.commit[1].signature
        fc.commit[1] = fc.commit[1]._replace(
            signature=sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    else:
        fc.commit[1] = fc.commit[1]._replace(type=1)
    out = follow(a, [good, fc])
    assert (out.height, out.refused_at, out.kind) == (1, 2, kind)
    assert out.trusted == plain(a)[0] and out.why


def test_a_height_left_to_the_others_skips_openssl_and_nothing_else():
    a = vals(10, 10, 10, 10)
    fc = full_commit(1, a)
    fc.commit[0] = fc.commit[0]._replace(signature=b"\x00" * 64)
    assert follow(a, [fc]).kind == literef.SIGNATURE
    assert follow(a, [fc], check_signatures=lambda h: False).height == 1
    fc = full_commit(1, a, absent=a[:2])
    out = follow(a, [fc], check_signatures=lambda h: False)
    assert (out.refused_at, out.kind) == (1, literef.QUORUM)
    for bad in (fc._replace(commit=fc.commit[:3]),
                full_commit(1, a)._replace(
                    header=dict(full_commit(1, a).header, height=1),
                    commit=[v._replace(round=v.timestamp_ns % 2)
                            for v in full_commit(1, a).commit])):
        assert literef.commit_unverified(bad) is not None
        assert commitref.verify_commit(
            CHAIN, bad.validators, bad.block_id, 1, bad.commit) is not None


# ------------------------------------- its hashes are the program's own

def test_the_two_hashes_are_the_programs():
    from tendermint_tpu.types import encoding
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    for n in (1, 3, 4, 7):
        members = vals(*range(5, 5 + n))
        vs = ValidatorSet([Validator(v.pub, v.power) for v in members])
        wire = encoding.cdumps(vs.to_obj())
        parsed = literef.parse_validators(wire)
        assert parsed == [(v.pubkey, v.voting_power) for v in vs.validators]
        assert literef.validators_hash(parsed) == vs.hash()
        assert [literef.address_of(p) for p, _ in parsed] == [
            v.address for v in vs.validators]
    header = Header(chain_id=CHAIN, height=7, time_ns=7,
                    validators_hash=vs.hash(), app_hash=b"\x03" * 32)
    assert literef.header_hash(
        encoding.cloads(encoding.cdumps(header.to_obj()))) == header.hash()
