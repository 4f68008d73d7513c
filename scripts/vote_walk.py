"""What does one commit's vote walk cost on this host? The native walk
(native/prep.cpp walk_votes, one call a commit) against the Python loop
it stands in for (types/validator_set._walk_votes), and the whole of
ValidatorSet.commit_verification_items around each, at the three shapes
the benchmark's cells send.

    python scripts/vote_walk.py [--rounds 7]

Shapes: `64v_one_ts` is chain_64v's commit (64 votes under one
timestamp, one sign-bytes), `100v_ts_each` chain_100v_churn's and
chain_100v_join's (100 votes, a timestamp and so a sign-bytes each),
`10kv_ts_each` commit_10kv's (10,000 votes, a timestamp each). Every
round walks commits decoded afresh (Commit.from_obj, as the drivers
decode between passes), so no vote has been read before; each commit's
native result is checked against the loop's (the signature objects by
identity, the sign-bytes, both index arrays). One JSON line a shape:
microseconds a commit, the best and the median of the rounds. It
touches no device; run it on the host in question
(`chiprun -- python scripts/vote_walk.py`)."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import statistics
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHAIN, HEIGHT = "vote-walk", 7
SHAPES = {              # name -> (votes, a timestamp each, commits)
    "64v_one_ts": (64, False, 512),
    "100v_ts_each": (100, True, 512),
    "10kv_ts_each": (10_000, True, 8),
}


def digest(tag: str, i: int, n: int = 32) -> bytes:
    return hashlib.sha512(b"%s %d" % (tag.encode(), i)).digest()[:n]


def commit_objs(votes: int, ts_each: bool, commits: int) -> list:
    """Wire objects of `commits` commits over one block id; the
    signatures are noise (nothing here verifies)."""
    from tendermint_tpu.types.block import BlockID, Commit, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType
    out = []
    for c in range(commits):
        bid = BlockID(digest("block", c), PartSetHeader(1, digest("parts", c)))
        out.append(Commit(bid, [
            Vote(digest("addr", i, 20), i, HEIGHT, 0,
                 1_700_000_000_000_000_000 + c * 1_000_003
                 + (i * 7_919 if ts_each else 0),
                 VoteType.PRECOMMIT, bid, digest("sig", c * votes + i, 64))
            for i in range(votes)]).to_obj())
    return out


def timed(fn, commits: list) -> float:
    """Microseconds a commit of one call of fn on each."""
    t0 = time.perf_counter()
    for c in commits:
        fn(c)
    return (time.perf_counter() - t0) * 1e6 / len(commits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=7)
    args = ap.parse_args(argv)
    from tendermint_tpu import native
    from tendermint_tpu.types import validator_set as vs
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.vote import VoteType, sign_bytes_template
    print(json.dumps({"sched_getaffinity": len(os.sched_getaffinity(0)),
                      "cpu_count": os.cpu_count(),
                      "tmprep": native.status()["_tmprep"]}), flush=True)
    if native.walk_votes([], HEIGHT, 0, 2, None) is None:
        print(json.dumps({"error": "no native walk on this host"}))
        return 1
    for name, (votes, ts_each, commits) in SHAPES.items():
        objs = commit_objs(votes, ts_each, commits)
        valset = vs.ValidatorSet(
            [vs.Validator(digest("key", i), 10 + i % 7)
             for i in range(votes)])

        def walk(how, c):
            def template(b):
                return sign_bytes_template(
                    CHAIN, b, HEIGHT, 0, VoteType.PRECOMMIT) \
                    + (b == c.block_id,)
            if how == "native":
                return native.walk_votes(c.precommits, HEIGHT, 0,
                                         VoteType.PRECOMMIT, template)
            return vs._walk_votes(c.precommits, HEIGHT, 0,
                                  VoteType.PRECOMMIT, template)

        def items(c):
            return valset.commit_verification_items(
                CHAIN, c.block_id, HEIGHT, c)

        for c in map(Commit.from_obj, objs):
            got, want = walk("native", c), walk("pure", c)
            assert all(a is b for a, b in zip(got[0], want[0])), name
            assert got[1] == want[1] and got[4:] == want[4:], name
            for k in (2, 3):
                assert got[k].dtype == want[k].dtype
                assert got[k].tobytes() == want[k].tobytes(), name
        # case -> (what is timed, whether native.walk_votes is there)
        cases = {"walk_pure": (lambda c: walk("pure", c), True),
                 "walk_native": (lambda c: walk("native", c), True),
                 "items_pure": (items, False), "items_native": (items, True)}
        line = {"shape": name, "votes": votes, "commits": commits,
                "sign_bytes_a_commit": votes if ts_each else 1}
        reads = {case: [] for case in cases}
        held = native.walk_votes
        for _ in range(args.rounds):    # the cases take turns: one
            for case, (fn, loaded) in cases.items():    # noisy second
                fresh = list(map(Commit.from_obj, objs))    # hits all
                if not loaded:
                    native.walk_votes = lambda *a: None
                try:
                    reads[case].append(timed(fn, fresh))
                finally:
                    native.walk_votes = held
        for case, us in reads.items():
            line[case + "_us"] = [round(min(us), 2),
                                  round(statistics.median(us), 2)]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
