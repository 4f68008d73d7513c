"""The collect phase for a commit that is not the collecting set's to
judge (ValidatorSet.commit_lanes_by_address): one lane a vote whatever
the two sizes, each under the key the set holds for the vote's address,
with the sign-bytes of commit_verification_items. Judged by
check_commit_lanes under the live set it accepts and refuses exactly as
verify_commit under that set does, with its messages, over seeded
histories of a set that is replaced, grows and shrinks and commits with
absent votes, nil votes, forged and misattributed signatures; a commit
no set would take is refused by the collect phase with verify_commit's
own words."""

import numpy as np
import pytest

import test_live_judge
from tendermint_tpu.models.verifier import default_verifier
from tendermint_tpu.types.block import BlockID, PartSetHeader
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import VoteType

from test_fast_sync_churn import SecpSigner
from test_live_judge import (POWER, SIGNATURE, SIZE, STEPS, TRIALS, History,
                             said)

SEEDS = [1, 2, 3, 5, 8, 13, 2**31 + 21]


@pytest.fixture
def a_secp256k1_member(monkeypatch):
    """Every fourth key a History makes is a secp256k1 key: its sets
    travel as triples."""
    made, openssl = [], test_live_judge.openssl_signer

    def signer(seed):
        made.append(seed)
        return SecpSigner(seed) if len(made) % 4 == 2 else openssl(seed)
    monkeypatch.setattr(test_live_judge, "openssl_signer", signer)


def trial_sets(hist, rng):
    """(collected, live, signing): a window's set one or two windows
    stale, the set in force, and the set that signed (the live one, or
    one a peer's lie or a header one set off would name)."""
    at = rng.randrange(STEPS + 1)
    live_step = min(STEPS, at + rng.choice((0, 0, 1, 1, 2, 3, 6)))
    signing = hist.sets[rng.choice(
        (live_step, live_step, live_step, at, min(STEPS, live_step + 1)))]
    return hist.sets[at], hist.sets[live_step], signing, live_step


def judged_through_lanes(seed, keys):
    hist = History(seed)
    rng, verifier = hist.rng, default_verifier()
    chain_id = f"lanes-{seed}"
    verdicts, taken = set(), {"smaller": 0, "larger": 0, "same": 0}
    for trial in range(TRIALS):
        collected, live, signing, live_step = trial_sets(hist, rng)
        height = 2 + trial
        block_id = BlockID(rng.randbytes(32),
                           PartSetHeader(1, rng.randbytes(32)))
        commit, done = hist.commit(signing, height, block_id, chain_id,
                                   live_step)
        want = said(lambda: live.verify_commit(
            chain_id, block_id, height, commit, verifier=verifier))
        lanes, for_block = collected.commit_lanes_by_address(
            chain_id, block_id, height, commit)
        votes = [pc for pc in commit.precommits if pc is not None]
        # one lane a vote, under a key the collecting set holds, over
        # the vote's own sign-bytes
        assert len(lanes) == len(for_block) == len(votes)
        assert isinstance(lanes, SigColumns) == (keys == "ed25519")
        held = {v.pubkey for v in collected.validators}
        known = {v.address: v.pubkey for v in collected.validators}
        for vote, flag, (key, msg, sig) in zip(votes, for_block, lanes):
            assert key in held
            assert key == known.get(vote.validator_address, key)
            assert msg == vote.sign_bytes(chain_id)
            assert sig == vote.signature
            assert flag == (vote.block_id == block_id)
        ok = verifier.verify(lanes)
        got = said(lambda: live.check_commit_lanes(
            commit, lanes, ok, for_block, verifier))
        assert got == want, (trial, done, len(collected), len(live))
        verdicts.add(want if want is None else
                     next(m for m in (SIGNATURE, POWER, SIZE) if m in want))
        if want is None:
            size = len(commit.precommits)
            taken["smaller" if len(collected) < size else
                  "larger" if len(collected) > size else "same"] += 1
    return verdicts, taken


@pytest.mark.parametrize("seed", SEEDS)
def test_lanes_by_address_judged_live_are_verify_commit(seed):
    verdicts, _taken = judged_through_lanes(seed, "ed25519")
    assert {None, SIGNATURE} <= verdicts


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_a_set_with_a_secp256k1_member_travels_as_triples(
        a_secp256k1_member, seed):
    verdicts, _taken = judged_through_lanes(seed, "mixed")
    assert None in verdicts


def test_commits_of_every_size_were_taken_and_every_refusal_reached():
    """A collecting set smaller than the commit, larger, and of its
    size each led to an accepted commit, and each of verify_commit's
    refusals came up, over the seeds."""
    verdicts, taken = set(), {"smaller": 0, "larger": 0, "same": 0}
    for seed in range(60, 66):
        v, t = judged_through_lanes(seed, "ed25519")
        verdicts |= v
        for k in taken:
            taken[k] += t[k]
    assert verdicts == {None, SIGNATURE, POWER, SIZE}
    assert all(taken.values()), taken


STRUCTURE = {
    "another_round": (lambda v: setattr(v, "round", 1),
                      "commit vote height/round mismatch"),
    "another_height": (lambda v: setattr(v, "height", v.height + 1),
                       "commit vote height/round mismatch"),
    "a_prevote": (lambda v: setattr(v, "type", VoteType.PREVOTE),
                  "commit contains non-precommit"),
}


@pytest.mark.parametrize("fault", sorted(STRUCTURE))
@pytest.mark.parametrize("collected_at", [0, STEPS])
def test_a_commit_no_set_would_take_is_refused_in_verify_commits_words(
        fault, collected_at):
    hist = History(9)
    signing = hist.sets[STEPS // 2]
    block_id = BlockID(b"\x03" * 32, PartSetHeader(1, b"\x04" * 32))
    commit, _done = hist.commit(signing, 5, block_id, "c", -1)
    while any(pc is None for pc in commit.precommits):
        commit, _done = hist.commit(signing, 5, block_id, "c", -1)
    change, words = STRUCTURE[fault]
    change(commit.precommits[2])
    collected = hist.sets[collected_at]
    got = said(lambda: collected.commit_lanes_by_address(
        "c", block_id, 5, commit))
    assert got == words == said(lambda: signing.verify_commit(
        "c", block_id, 5, commit, verifier=default_verifier()))
    assert said(lambda: collected.commit_lanes_by_address(
        "c", block_id, 6, commit)) == "commit height mismatch"


def test_an_address_the_set_does_not_hold_gets_a_row_all_the_same():
    """The vote's own slot, or the set's last row where the commit is
    the larger: any key would do, since the judge believes a verdict
    only for the key it was computed under; what matters is one lane a
    vote."""
    hist = History(10)
    small = ValidatorSet(hist.sets[0].validators[:3])
    strangers = ValidatorSet(
        [Validator(pk, 10) for pk in hist.standby[:5]])
    block_id = BlockID(b"\x05" * 32, PartSetHeader(1, b"\x06" * 32))
    commit, _done = hist.commit(strangers, 4, block_id, "c", -1)
    while any(pc is None for pc in commit.precommits):
        commit, _done = hist.commit(strangers, 4, block_id, "c", -1)
    assert small.rows_by_address(commit) == [0, 1, 2, 2, 2]
    lanes, for_block = small.commit_lanes_by_address("c", block_id, 4, commit)
    assert len(lanes) == 5
    assert (lanes.pk == small.columns().pk[[0, 1, 2, 2, 2]]).all()
    # a commit of its own members, in its own order: its own rows
    own, _done = hist.commit(small, 4, block_id, "c", -1)
    assert small.rows_by_address(own) == [
        i for i, pc in enumerate(own.precommits) if pc is not None]
    assert isinstance(for_block, np.ndarray)
