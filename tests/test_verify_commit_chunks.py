"""One commit that does not fit one device chunk, through the device
path (jnp kernels on this backend), at a toy size: BATCH_CHUNK patched
to 16 and 20 validators, so a commit is a full chunk of 16 and a tail
of 4 padded to the 8 bucket. And the spans the single-commit path
records (`commit.collect`, `commit.wait`, `commit.check`)."""

import threading

import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.models import verifier as verifier_mod
from tendermint_tpu.models.verifier import BatchVerifier
from tendermint_tpu.telemetry import trace
from tendermint_tpu.types import (BlockID, Commit, PartSetHeader, PrivKey,
                                  Validator, ValidatorSet, Vote)
from tendermint_tpu.types.vote import VoteType

CHAIN = "chunk-chain"
CHUNK, N = 16, 20
BLOCK = BlockID(b"B" * 32, PartSetHeader(1, b"p" * 32))
NIL = BlockID()
# stake 60 in all: 18 validators of 3, one of 1 and one of 5
POWERS = [3] * 18 + [1, 5]


@pytest.fixture(scope="module")
def net():
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(N)]
    valset = ValidatorSet([Validator(p.pubkey.ed25519, w)
                           for p, w in zip(privs, POWERS)])
    by_addr = {p.pubkey.address: p for p in privs}
    return valset, [by_addr[v.address] for v in valset.validators]


@pytest.fixture
def device_verifier(monkeypatch):
    monkeypatch.setattr(verifier_mod, "BATCH_CHUNK", CHUNK)
    return BatchVerifier("jax", mesh="off")


def commit_of(net, height, for_block=None):
    """Every validator precommits at `height` with a timestamp of its
    own; those not in `for_block` (all are, by default) sign nil."""
    valset, privs = net
    votes = []
    for idx, (val, priv) in enumerate(zip(valset.validators, privs)):
        bid = BLOCK if for_block is None or idx in for_block else NIL
        v = Vote(val.address, idx, height, 0, height * 10 ** 9 + idx,
                 VoteType.PRECOMMIT, bid)
        v.signature = priv.sign(v.sign_bytes(CHAIN))
        votes.append(v)
    return Commit(BLOCK, votes)


def indices_with_power(net, target):
    """Validator indices, some of them in the tail chunk, whose stake
    adds up to `target` (40: thirteen of 3 and the 1; 41: twelve of 3
    and the 5)."""
    valset, _ = net
    powers = [v.voting_power for v in valset.validators]
    odd = powers.index(1 if target == 40 else 5)
    threes = [i for i in reversed(range(N)) if powers[i] == 3]
    chosen = set(threes[:(target - powers[odd]) // 3]) | {odd}
    assert sum(powers[i] for i in chosen) == target
    assert any(i >= CHUNK for i in chosen)
    return chosen


def test_a_genuine_commit_crosses_the_chunk_boundary(net, device_verifier):
    valset, _ = net
    valset.verify_commit(CHAIN, BLOCK, 3, commit_of(net, 3),
                         verifier=device_verifier)
    assert device_verifier.stats["jax_sigs"] == N
    assert device_verifier.stats["calls"] == 1


@pytest.mark.parametrize("lane", [CHUNK - 1, CHUNK, N - 1])
def test_a_bad_signature_in_either_chunk_refuses(net, device_verifier, lane):
    valset, _ = net
    commit = commit_of(net, 4)
    sig = commit.precommits[lane].signature
    commit.precommits[lane].signature = sig[:7] + bytes([sig[7] ^ 4]) + sig[8:]
    with pytest.raises(ValueError, match="invalid signature"):
        valset.verify_commit(CHAIN, BLOCK, 4, commit,
                             verifier=device_verifier)
    assert device_verifier.stats["jax_sigs"] == N


@pytest.mark.parametrize("stake,accepted", [(40, False), (41, True)])
def test_the_stake_boundary_across_chunks(net, device_verifier, stake,
                                          accepted):
    """40 of 60 is exactly two thirds and is refused; 41 is accepted.
    The others sign nil, validly: all 20 signatures are verified."""
    valset, _ = net
    commit = commit_of(net, 5, indices_with_power(net, stake))
    if accepted:
        valset.verify_commit(CHAIN, BLOCK, 5, commit,
                             verifier=device_verifier)
    else:
        with pytest.raises(ValueError, match="insufficient voting power: 40"):
            valset.verify_commit(CHAIN, BLOCK, 5, commit,
                                 verifier=device_verifier)
    assert device_verifier.stats["jax_sigs"] == N


# ----------------------------------------------------------------- spans

@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.TRACER.clear()
    yield
    telemetry.set_enabled(was)


def test_commit_spans_carry_the_height_and_hold_the_fetch(
        net, device_verifier, telemetry_on):
    valset, _ = net
    finish = valset.verify_commit_async(CHAIN, BLOCK, 7, commit_of(net, 7),
                                        verifier=device_verifier)
    done = []
    th = threading.Thread(target=lambda: done.append(finish()))
    th.start()
    th.join()
    assert done == [None]
    by = {}
    for e in telemetry.TRACER.events():
        by.setdefault(e["name"], []).append(e)
    collect, wait, check = (by[n][0] for n in (
        "commit.collect", "commit.wait", "commit.check"))
    assert [len(by[n]) for n in ("commit.collect", "commit.wait",
                                 "commit.check")] == [1, 1, 1]
    assert collect["req"] == wait["req"] == check["req"] == 7
    assert {trace.SPANS[n] for n in ("commit.collect", "commit.wait",
                                     "commit.check")} == {"verifier"}
    # the dispatch is the caller's next step, not inside the collect
    (dispatch,) = by["verify.dispatch"]
    assert dispatch["parent"] == 0 and dispatch["tid"] == collect["tid"]
    assert dispatch["ts"] >= collect["ts"] + collect["dur"]
    assert len(by["verify.enqueue"]) == 2           # 16 + 4
    # the finisher's thread: the fetch nests in the wait and names the
    # dispatch as its cause; the check follows
    (fetch,) = by["verify.fetch"]
    assert fetch["parent"] == wait["id"] and fetch["tid"] == wait["tid"]
    assert fetch["cause"] == dispatch["id"]
    assert fetch["req"] == dispatch["req"]
    assert fetch["args"] == {"chunks": 2}
    assert wait["tid"] != collect["tid"]
    assert check["ts"] >= wait["ts"] + wait["dur"]


def test_a_refused_commit_still_closes_its_spans(net, telemetry_on):
    valset, _ = net
    commit = commit_of(net, 8, set(range(10)))      # 30 of 60 at most
    with pytest.raises(ValueError, match="insufficient"):
        valset.verify_commit(CHAIN, BLOCK, 8, commit,
                             verifier=BatchVerifier("python"))
    names = [e["name"] for e in telemetry.TRACER.events()
             if e["name"].startswith("commit.")]
    assert names == ["commit.collect", "commit.wait", "commit.check"]
    assert not trace._open_spans()
    with pytest.raises(ValueError, match="commit height mismatch"):
        valset.verify_commit(CHAIN, BLOCK, 9, commit,
                             verifier=BatchVerifier("python"))
    assert not trace._open_spans()


def test_commit_spans_off_leave_nothing_behind(net):
    was = telemetry.enabled()
    telemetry.set_enabled(False)
    try:
        telemetry.TRACER.clear()
        assert trace.span("commit.collect", req=1) is trace._NULL_SPAN
        ids = next(trace._ids)
        valset, _ = net
        valset.verify_commit(CHAIN, BLOCK, 3, commit_of(net, 3),
                             verifier=BatchVerifier("python"))
        assert telemetry.TRACER.events() == []
        assert next(trace._ids) == ids + 1      # no span was made
        assert not trace._open_spans()
    finally:
        telemetry.set_enabled(was)


# ------------------------------------------------- signing for a large set

def test_signing_params_hold_a_10k_validator_set(monkeypatch):
    """Signing 32 commits of 10,000 validators asks for each seed's
    parameters 32 times: the cache holds the whole set, so each public
    key is derived once (at 4,096 entries it was derived every time,
    2.4 ms each in pure Python: 12 minutes of a chip run's set-up)."""
    from tendermint_tpu.ops import ed25519
    from tendermint_tpu.utils import ed25519_ref as ref
    seed = b"\x09" * 32
    monkeypatch.setattr(ed25519, "_sign_params_cache", {})
    a, prefix, pk = ed25519.signing_params(seed)
    assert pk == ref.public_key(seed) == PrivKey.generate(seed).pubkey.ed25519
    assert len(a) == len(prefix) == 32
    derived = []
    monkeypatch.setattr(ed25519, "_public_key",
                        lambda s: derived.append(s) or b"\x00" * 32)
    seeds = [i.to_bytes(32, "big") for i in range(10_000)]
    for _ in range(2):
        for s in seeds:
            ed25519.signing_params(s)
    assert len(derived) == 10_000
    # one seed more than the cache holds puts the oldest out, not the set
    room = ed25519._PREDECOMP_MAX_KEYS
    more = [i.to_bytes(32, "big") for i in range(10_000, room + 1)]
    for s in more:
        ed25519.signing_params(s)
    assert len(ed25519._sign_params_cache) == room
    assert seeds[0] not in ed25519._sign_params_cache
    del derived[:]
    for s in seeds[1:] + more:
        ed25519.signing_params(s)
    assert derived == []
