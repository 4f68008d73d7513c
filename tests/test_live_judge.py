"""The live judge of fast-sync's window engine
(ValidatorSet.check_commit_lanes): lanes that one set paired with their
keys, judged under another. Over seeded histories of a validator set
(stake changes, replacements, joins, leaves) and commits that are
honest or tampered with, it accepts and refuses exactly as verify_commit
under the live set does, with its message, whichever set collected the
lanes and however they were paired; a judge that takes a lane's key on
trust does not get past a join; and what the window's third element
says guards nothing."""

import random

import numpy as np
import pytest

from benchmark.joinchain import JoinChain, departed_signs_for_joiner
from benchmark.kvref import openssl_signer
from tendermint_tpu.models.verifier import default_verifier
from tendermint_tpu.types.block import BlockID, Commit, PartSetHeader
from tendermint_tpu.types.sigcolumns import SigColumns
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote, VoteType

from test_fast_sync_churn import serial, stopped_at, sync

N_VALS, STEPS, TRIALS = 7, 10, 48
SIGNATURE, POWER, SIZE = ("invalid signature in commit",
                          "insufficient voting power", "commit size")


class History:
    """Sets S0..S_STEPS, each one seeded change above the last, and who
    left and joined at each replacement."""

    def __init__(self, seed):
        rng = self.rng = random.Random(f"{seed}/live-judge")
        self.sign = {}
        for _ in range(N_VALS + STEPS):
            key = openssl_signer(rng.randbytes(32))
            self.sign[key.public_key().public_bytes_raw()] = key.sign
        pubs = list(self.sign)
        self.standby = pubs[N_VALS:]
        ranks = rng.sample(range(1, N_VALS + 1), N_VALS)
        self.sets = [ValidatorSet([Validator(pk, 1_000_000 // (r + 2))
                                   for pk, r in zip(pubs, ranks)])]
        self.replaced = {}      # step -> (the key that left, the joiner)
        for step in range(1, STEPS + 1):
            self.sets.append(self._moved(self.sets[-1], step))

    def _moved(self, vs, step):
        rng, vals = self.rng, vs.validators
        kind = rng.choice(("stake", "stake", "replace", "replace", "join",
                           "leave"))
        least = min(vals, key=lambda v: (v.voting_power, v.address))
        if kind == "leave" and len(vals) <= N_VALS - 1:
            kind = "join"
        if kind == "stake":
            v = rng.choice(vals)
            # now and then far enough to move the quorum
            power = max(1, v.voting_power * rng.choice((95, 104, 30, 400))
                        // 100)
            return vs.update_with_changes([Validator(v.pubkey, power)])
        if kind == "leave":
            return vs.update_with_changes([Validator(least.pubkey, 0)])
        new = Validator(self.standby.pop(0), least.voting_power + 1)
        if kind == "join":
            return vs.update_with_changes([new])
        self.replaced[step] = (least.pubkey, new.pubkey)
        return vs.update_with_changes([Validator(least.pubkey, 0), new])

    def commit(self, signing, height, block_id, chain_id, live_step):
        """A commit `signing` signed, then tampered with or not: (the
        commit, what was done to it)."""
        rng, vals = self.rng, signing.validators
        nil = BlockID()
        done = rng.choice(("honest", "honest", "absent", "nil", "weak",
                           "forged", "claims_another", "departed_signs",
                           "departed_votes"))
        votes = []
        for i, v in enumerate(vals):
            bid = block_id
            if done == "nil" and rng.random() < 0.2 or \
                    done == "weak" and rng.random() < 0.45:
                bid = nil
            vote = Vote(v.address, i, height, 0, height * 10**9 + i,
                        VoteType.PRECOMMIT, bid)
            vote.signature = self.sign[v.pubkey](vote.sign_bytes(chain_id))
            votes.append(vote)
        i = rng.randrange(len(votes))
        if done == "absent":
            for j in rng.sample(range(len(votes)), rng.choice((1, 2))):
                votes[j] = None
        elif done == "forged":
            sig = bytearray(votes[i].signature)
            sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            votes[i].signature = bytes(sig)
        elif done == "claims_another":
            # slot i claims validator k's address and bears k's good
            # signature over the same sign-bytes
            k = (i + 1 + rng.randrange(len(votes) - 1)) % len(votes)
            votes[i].validator_address = vals[k].address
            votes[i].signature = self.sign[vals[k].pubkey](
                votes[i].sign_bytes(chain_id))
        elif done in ("departed_signs", "departed_votes"):
            below = [s for s in self.replaced if s <= live_step]
            if not below:
                return Commit(block_id, votes), "honest"
            departed, joiner = self.replaced[max(below)]
            slot = next((j for j, v in enumerate(vals)
                         if v.pubkey == joiner), None)
            if slot is None:
                return Commit(block_id, votes), "honest"
            # the joiner's slot bears the departed key's good signature,
            # under the joiner's address or under the departed's own
            if done == "departed_votes":
                votes[slot].validator_address = Validator(departed, 1).address
            votes[slot].signature = self.sign[departed](
                votes[slot].sign_bytes(chain_id))
        return Commit(block_id, votes), done


def said(judge):
    try:
        judge()
    except ValueError as e:
        return str(e)
    return None


@pytest.mark.parametrize("form", ["columns", "triples"])
@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 13, 2**31 + 21])
def test_the_live_judge_is_verify_commit_under_the_live_set(seed, form):
    hist = History(seed)
    rng, verifier = hist.rng, default_verifier()
    chain_id = f"judge-{seed}"
    verdicts, doings, verified_again, no_lanes = set(), set(), 0, 0
    for trial in range(TRIALS):
        # a window is collected under a set one or two windows stale,
        # and applies across every change in between
        at = rng.randrange(STEPS + 1)
        live_step = min(STEPS, at + rng.choice((0, 0, 1, 1, 2, 3, 6)))
        collected, live = hist.sets[at], hist.sets[live_step]
        # the set that signed is the live one, or (a peer's lie, or a
        # header one set off) the collection set or the one above
        signing = hist.sets[rng.choice(
            (live_step, live_step, live_step, at,
             min(STEPS, live_step + 1)))]
        height = 2 + trial
        block_id = BlockID(rng.randbytes(32),
                           PartSetHeader(1, rng.randbytes(32)))
        commit, done = hist.commit(signing, height, block_id, chain_id,
                                   live_step)
        want = said(lambda: live.verify_commit(
            chain_id, block_id, height, commit, verifier=verifier))
        try:
            items, power = collected.commit_verification_items(
                chain_id, block_id, height, commit)
        except ValueError:
            no_lanes += 1       # the engine's `sync.reverify` branch
            continue
        assert isinstance(items, SigColumns)
        by_address = SigColumns(
            collected.columns().pk[collected.rows_by_address(commit)],
            items.sigs, items.msgs, items.idx)
        for lanes in (items, by_address):
            if form == "triples":
                lanes = list(lanes)
            ok = verifier.verify(lanes)
            holder = {}
            got = said(lambda: holder.update(n=live.check_commit_lanes(
                commit, lanes, ok, power.for_block, verifier)))
            assert got == want, (trial, done, at, live_step)
            if len(live) == len(commit.precommits):
                slots = [i for i, pc in enumerate(commit.precommits)
                         if pc is not None]
                assert holder.get("n", 0) <= len(slots)
                if "n" in holder:
                    assert holder["n"] == sum(
                        live.validators[s].pubkey != lane[0]
                        for s, lane in zip(slots, lanes))
                    verified_again += holder["n"]
        verdicts.add(want if want is None else
                     next(m for m in (SIGNATURE, POWER, SIZE) if m in want))
        doings.add(done)
    # the history and the tampering reached what they are there for
    assert {None, SIGNATURE} <= verdicts and len(doings) >= 6
    assert verified_again > 0 and hist.replaced and no_lanes < TRIALS // 2


def test_every_refusal_was_reached_over_the_seeds():
    """Insufficient power and a live set of another size, which a
    single seed may miss, each come up over a few."""
    seen = set()
    verifier = default_verifier()
    for seed in range(40, 46):
        hist = History(seed)
        for trial in range(TRIALS):
            at = hist.rng.randrange(STEPS)
            collected, live = hist.sets[at], hist.sets[
                min(STEPS, at + hist.rng.choice((1, 2, 4)))]
            block_id = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x08" * 32))
            commit, _done = hist.commit(collected, 9, block_id, "c", at)
            try:
                items, power = collected.commit_verification_items(
                    "c", block_id, 9, commit)
            except ValueError:
                continue
            ok = verifier.verify(items)
            want = said(lambda: live.verify_commit("c", block_id, 9, commit,
                                                   verifier=verifier))
            assert said(lambda: live.check_commit_lanes(
                commit, items, ok, power.for_block, verifier)) == want
            seen.add(want and next(m for m in (SIGNATURE, POWER, SIZE)
                                   if m in want))
    assert seen == {None, SIGNATURE, POWER, SIZE}


def test_a_lane_without_a_verdict_gets_none_from_the_judge():
    """A verdict vector shorter than the lanes (the benchmark's
    `truncate` control): only lanes that have a verdict count, and a
    stale lane beyond them is not verified either."""
    hist = History(4)
    step = min(hist.replaced)
    collected, live = hist.sets[step - 1], hist.sets[step]
    block_id = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    commit, _ = hist.commit(live, 3, block_id, "c", -1)     # honest
    items, power = collected.commit_verification_items(
        "c", block_id, 3, commit)
    verifier = default_verifier()
    ok = verifier.verify(items)
    stale = [i for i, (v, lane) in enumerate(zip(live.validators, items))
             if v.pubkey != lane[0]]
    assert stale and not ok[stale].any()
    assert live.check_commit_lanes(commit, items, ok, power.for_block,
                                   verifier) == len(stale)
    short = ok[:stale[0]]
    before = verifier.stats["sigs"]
    judged = said(lambda: live.check_commit_lanes(
        commit, items, short, power.for_block, verifier))
    assert verifier.stats["sigs"] == before
    live_power = live.commit_verification_items("c", block_id, 3, commit)[1]
    assert judged == said(
        lambda: live.check_commit_results(short, live_power))
    assert (judged is None) == (
        live_power.tally_of(len(short)) * 3 > live.columns().total * 2)


# ----------------------------------------------------- through the engine

def rehearsal_chain():
    """The join cell's rehearsal: 8 validators, 48 blocks, 12 changes
    of stake and 4 of membership."""
    return JoinChain(5, 48, 8, 12, 4, 4, 64, 8)


def first_join(chain):
    return min(chain.joined_at)


@pytest.mark.parametrize("window", [4, 8])
def test_a_judge_that_takes_a_lanes_key_on_trust_does_not_get_through(
        monkeypatch, window):
    """The mutation that a change to the key comparison must not
    survive: a judge to which every lane's key is the live one either
    refuses the honest chain at its first join (the joiner's lane was
    verified under a key of the set before) or takes the chain on which
    the departed key signs for the joiner. With the join's repair out
    of the way (PR 48: it verifies the joiner's lanes under the live
    key before their blocks are judged, so that this judge would meet
    no lane under another key), as for every lane a repair did not
    foresee."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    monkeypatch.setattr(BlockchainReactor, "_repair",
                        lambda self, *a: None)
    chain = rehearsal_chain()
    join = first_join(chain)
    at, tampered = departed_signs_for_joiner(chain, join)

    def both():
        reactor, error = sync(chain, window)
        honest = (error, reactor.state.last_block_height)
        reactor, error = sync(chain, window, tampered)
        return honest, (error, stopped_at(reactor))

    honest, lied = both()
    assert honest == (None, 48) and lied == (None, (at - 1, True))

    judge = ValidatorSet.check_commit_lanes

    def on_trust(self, commit, lanes, ok, for_block, verifier):
        slots = [i for i, pc in enumerate(commit.precommits)
                 if pc is not None]
        if len(self.validators) == len(commit.precommits):
            lanes = SigColumns(self.columns().pk[slots], lanes.sigs,
                               lanes.msgs, lanes.idx)
        return judge(self, commit, lanes, ok, for_block, verifier)
    monkeypatch.setattr(ValidatorSet, "check_commit_lanes", on_trust)
    honest, lied = both()
    refuses_the_honest_chain = honest[1] == join < 48
    takes_the_tampered_chain = lied[1][0] >= at
    assert refuses_the_honest_chain or takes_the_tampered_chain


@pytest.mark.parametrize("window", [4, 8])
def test_the_windows_hash_guards_nothing(monkeypatch, window):
    """tests/benchrec's retired control swapped the collected window's
    set hash for an object equal to everything and wanted the sync to
    fail: lanes are judged by their keys, so the swap changes nothing,
    on the honest chain and on the tampered one."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    class AnyHash:
        def __eq__(self, other):
            return True
    collect = BlockchainReactor._collect_window

    def collected_under_whatever_set(self, skip):
        out = collect(self, skip)
        return out and (out[0], out[1], AnyHash(), out[3])
    monkeypatch.setattr(BlockchainReactor, "_collect_window",
                        collected_under_whatever_set)
    chain = rehearsal_chain()
    reactor, error = sync(chain, window)
    assert error is None and reactor.state.last_block_height == 48
    want, _sets, _apps = serial(chain)
    assert reactor.state.to_obj() == want.to_obj()
    for join in sorted(chain.joined_at)[:2]:
        at, tampered = departed_signs_for_joiner(chain, join)
        reactor, error = sync(chain, window, tampered)
        assert error is None and stopped_at(reactor) == (at - 1, True)
