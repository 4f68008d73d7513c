"""The plain VerifyCommit reference (benchmark/commitref.py) against
ValidatorSet.verify_commit on seeded random commits: the same verdict
on each, and the same sign-bytes on seeded votes. The reference imports
nothing of the program; this test is where the two meet."""

import random

import pytest

from benchmark import commitref, commits
from benchmark.commitref import NIL_BLOCK, PRECOMMIT, PlainVote
from benchmark.commits import program_block_id
from tendermint_tpu.models.verifier import BatchVerifier
from tendermint_tpu.types import PrivKey, Validator, ValidatorSet, Vote

CHAIN = "commitref-chain"
PYV = BatchVerifier("python")
GROUPS, PER_GROUP = 10, 24          # 240 commits
KINDS = ("plain", "boundary", "boundary_plus", "bad_signature", "height",
         "round", "type", "short", "empty")


def program_commit(valset, block_id, votes):
    return commits.program_commit([v.address for v in valset.validators],
                                  block_id, votes)


def subset_with_power(powers, target):
    """Indices whose powers add up to `target`, or None."""
    reach = {0: []}
    for i, p in enumerate(powers):
        for s, idxs in list(reach.items()):
            reach.setdefault(s + p, idxs + [i])
    return reach.get(target)


def random_commit(rng):
    """(valset, plain validators, block id, height, plain votes, kind)"""
    n = rng.randrange(7, 41)
    privs = [PrivKey.generate(rng.randbytes(32)) for _ in range(n)]
    valset = ValidatorSet([Validator(p.pubkey.ed25519, rng.randrange(1, 31))
                           for p in privs])
    by_addr = {p.pubkey.address: p for p in privs}
    privs = [by_addr[v.address] for v in valset.validators]
    powers = [v.voting_power for v in valset.validators]
    total = sum(powers)
    height, round_ = rng.randrange(1, 10 ** 6), rng.randrange(0, 3)
    block = (rng.randbytes(32), rng.randrange(1, 5), rng.randbytes(32))
    other = (rng.randbytes(32), 1, rng.randbytes(32))
    kind = rng.choices(KINDS, (6, 1, 2, 1, 1, 1, 1, 1, 1))[0]

    for_block = None
    if kind in ("boundary", "boundary_plus"):
        # the stake for the block is exactly 2/3 of the whole (the most
        # that is refused), or the least above it
        target = 2 * total // 3 + (kind == "boundary_plus")
        for_block = subset_with_power(powers, target)
        if for_block is None:
            kind = "plain"
    votes = []
    for idx in range(n):
        if for_block is not None:
            bid = block if idx in for_block else rng.choice((NIL_BLOCK, other))
        else:
            bid = rng.choices((block, NIL_BLOCK, other, None),
                              (16, 1, 1, 2))[0]
        if bid is None:
            votes.append(None)
            continue
        votes.append(PlainVote(height, round_, PRECOMMIT,
                               rng.randrange(1, 10 ** 18), bid, b""))
    present = [i for i, v in enumerate(votes) if v is not None]
    if kind == "empty":
        votes, present = [None] * n, []
    if present:
        lane = rng.choice(present)
        if kind == "height":
            votes[lane] = votes[lane]._replace(height=height + 1)
        elif kind == "round":
            votes[lane] = votes[lane]._replace(round=round_ + 1)
        elif kind == "type":
            votes[lane] = votes[lane]._replace(type=1)
    votes = [v if v is None else v._replace(signature=privs[i].sign(
        commitref.sign_bytes(CHAIN, v))) for i, v in enumerate(votes)]
    if kind == "bad_signature" and present:
        lane = rng.choice(present)
        votes[lane] = votes[lane]._replace(signature=commits.flip_bit(
            votes[lane].signature, rng.randrange(64)))
    if kind == "short":
        votes = votes[:-1]
    validators = [(v.pubkey, v.voting_power) for v in valset.validators]
    return valset, validators, block, height, votes, kind


@pytest.mark.parametrize("group", range(GROUPS))
def test_reference_and_program_give_the_same_verdict(group):
    rng = random.Random(f"commitref/{group}")
    verdicts = []
    for _ in range(PER_GROUP):
        valset, validators, block, height, votes, kind = random_commit(rng)
        want = commitref.verify_commit(CHAIN, validators, block, height,
                                       votes)
        try:
            valset.verify_commit(CHAIN, program_block_id(block), height,
                                 program_commit(valset, block, votes),
                                 verifier=PYV)
            said = None
        except ValueError as e:
            said = str(e)
        assert (want is None) == (said is None), (kind, want, said)
        if kind == "boundary":
            assert want.startswith("insufficient")
        elif kind == "boundary_plus":
            assert want is None
        verdicts.append(want is None)
    # both answers are given, often
    assert 3 <= sum(verdicts) <= PER_GROUP - 3


def test_the_stake_boundary_is_strict():
    """Exactly two thirds of the stake is refused and one more unit of
    stake is accepted, by reference and program alike (unequal stake)."""
    rng = random.Random("commitref/boundary")
    privs = [PrivKey.generate(rng.randbytes(32)) for _ in range(9)]
    valset = ValidatorSet([Validator(p.pubkey.ed25519, w) for p, w in
                           zip(privs, (1, 2, 3, 4, 5, 6, 7, 8, 9))])
    by_addr = {p.pubkey.address: p for p in privs}
    privs = [by_addr[v.address] for v in valset.validators]
    powers = [v.voting_power for v in valset.validators]
    validators = [(v.pubkey, v.voting_power) for v in valset.validators]
    block = (b"b" * 32, 1, b"p" * 32)
    for target, accepted in ((30, False), (31, True)):      # of 45
        for_block = subset_with_power(powers, target)
        votes = []
        for idx, priv in enumerate(privs):
            v = PlainVote(5, 0, PRECOMMIT, 77 + idx,
                          block if idx in for_block else NIL_BLOCK, b"")
            votes.append(v._replace(signature=priv.sign(
                commitref.sign_bytes(CHAIN, v))))
        assert (commitref.verify_commit(
            CHAIN, validators, block, 5, votes) is None) is accepted
        commit = program_commit(valset, block, votes)
        if accepted:
            valset.verify_commit(CHAIN, program_block_id(block), 5, commit,
                                 verifier=PYV)
        else:
            with pytest.raises(ValueError, match="insufficient"):
                valset.verify_commit(CHAIN, program_block_id(block), 5,
                                     commit, verifier=PYV)


@pytest.mark.parametrize("seed", range(4))
def test_reference_sign_bytes_are_the_programs(seed):
    rng = random.Random(f"commitref/sign-bytes/{seed}")
    for _ in range(50):
        chain_id = rng.choice(("c", "bench-commit-000000000007",
                               'quo"te\\d', "ünï-chain"))
        bid = rng.choice((NIL_BLOCK, (rng.randbytes(32), rng.randrange(1, 99),
                                      rng.randbytes(32))))
        plain = PlainVote(rng.randrange(1, 2 ** 40), rng.randrange(0, 9),
                          rng.choice((1, 2)), rng.randrange(0, 2 ** 62), bid,
                          b"")
        vote = Vote(rng.randbytes(20), rng.randrange(0, 10 ** 4),
                    plain.height, plain.round, plain.timestamp_ns, plain.type,
                    program_block_id(bid))
        assert commitref.sign_bytes(chain_id, plain) == \
            vote.sign_bytes(chain_id)


def test_reference_imports_nothing_of_the_program():
    import ast
    import os
    path = os.path.join(os.path.dirname(commitref.__file__), "commitref.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for node in ast.walk(tree)
             if isinstance(node, ast.Import) for a in node.names]
    names += [node.module for node in ast.walk(tree)
              if isinstance(node, ast.ImportFrom)]
    assert names and not [n for n in names if n.startswith("tendermint_tpu")]
    assert set(names) <= {"__future__", "json", "typing",
                          "benchmark.kvref"}
