"""Driver `verify_commit`: a full node verifying one commit at a time.

Set-up makes the validator set and the commits from the seed (signed on
the device, held as wire bytes) and runs one whole untimed pass, so both
kernel shapes of a commit (the full chunk and the padded tail) are
compiled and the predecompression cache is in its steady `hit` state.
The window is whole passes over the same commits, each over objects
decoded afresh from the wire bytes between passes: for each height in
order ONE synchronous `ValidatorSet.verify_commit` on the process's
default verifier, as `state/validation.py` calls it before a node may
prevote. Nothing is pooled and nothing overlaps: concurrency 1, closed
loop. A pass keeps each call's seconds.

`correct`, after the window: no genuine commit refused; every signature
of the window verified on the device; a 256-signature sample of the
device's signatures byte-equal to OpenSSL's; the timed verifier's
verdicts on one whole commit's batch with seeded tampered lanes, a
quarter of them in the tail chunk, equal to OpenSSL's lane by lane; and
a seeded set of whole commits (a flipped signature in either chunk, the
stake boundary from both sides, absent validators, a short commit, a
vote of another height) through `verify_commit`, each verdict equal to
the plain reference's (`commitref.verify_commit`).
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import commitref, commits, probe
from benchmark.commits import CommitSet, commit_cases, flip_bit
from benchmark.harness import Outcome
from benchmark.kvref import openssl_signer, openssl_verify
from benchmark.passes import Pass


def tampered_commit(cs: CommitSet, height: int, chunk: int, rng) -> tuple:
    """(items, tampered lanes): one commit's triples with seeded lanes
    broken four ways, a quarter of them at or after `chunk`."""
    items = cs.items(height)
    n = len(items)
    n_lanes = min(40, n // 4)
    n_tail = max(1, n_lanes // 4) if n > chunk else 0
    lanes = sorted(rng.sample(range(min(chunk, n)), n_lanes - n_tail) +
                   rng.sample(range(chunk, n), n_tail))
    for k, lane in enumerate(lanes):
        pub, msg, sig = items[lane]
        if k % 4 == 0:
            sig = flip_bit(sig, 0)              # R
        elif k % 4 == 1:
            sig = flip_bit(sig, 32)             # s
        elif k % 4 == 2:
            msg = msg + b"x"
        else:
            pub = cs.pubkeys[(lane + 1) % n]
        items[lane] = (pub, msg, sig)
    return items, lanes


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.models import verifier as verifier_mod

    p = h.params
    n_vals, n_heights = int(p["validators"]), int(p["commit_heights"])
    chunk = int(p["verify_chunk"])
    if int(p["signers_per_commit"]) != n_vals:
        raise RuntimeError("this driver has every validator sign")
    if not h.rehearsal and verifier_mod.BATCH_CHUNK != chunk:
        raise RuntimeError(
            f"the program cuts a batch every {verifier_mod.BATCH_CHUNK} "
            f"signatures; the configuration states {chunk}")
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/verify_commit")
    n_sigs = n_heights * n_vals

    with h.spans.span("build_commits"):
        cs = CommitSet(h.seed, n_vals, n_heights,
                       int(p["voting_power_each"]))
    verifier = verifier_mod.default_verifier()
    held = {}

    def verify_each(valset, commits) -> tuple:
        """(genuine commits refused, each call's seconds)."""
        bad, secs = 0, []
        for block_id, height, commit in commits:
            t0 = time.perf_counter()
            try:
                valset.verify_commit(cs.chain_id, block_id, height, commit)
            except ValueError as e:
                h.note("refused", height=height, error=str(e)[:200])
                bad += 1
            secs.append(time.perf_counter() - t0)
        return bad, secs

    with h.spans.span("warm_pass"):
        if verify_each(*cs.decode())[0]:
            raise RuntimeError("the warm pass refused a genuine commit")
    h.settle()

    def between():
        held.clear()
        gc.collect()
        with h.spans.span("decode"):
            held["valset"], held["commits"] = cs.decode()
        return held

    def timed(prepared) -> Pass:
        with h.spans.span("verify_commits"):
            t0 = time.perf_counter()
            bad, secs = verify_each(prepared["valset"], prepared["commits"])
            dt = time.perf_counter() - t0
        return Pass(t0, dt, n_heights, bad, extra={"call_s": secs})

    with probe.VerifierTap(verifier, h.spans, p.get("control")):
        passes, counters = h.timed_passes(timed, between, verifier)
        held.clear()
        gc.collect()

        # ---- what the window produced, against the plain references
        h.check("genuine_commits_refused", sum(q.failed for q in passes), 0)
        h.check_signatures(counters, n_sigs * len(passes))
        sample = range(0, n_sigs, max(1, n_sigs // 256))
        h.check("device_signatures_differing_from_openssl", sum(
            1 for i in sample
            if cs.sigs[i] != openssl_signer(
                cs.seeds[i % n_vals]).sign(cs.msgs[i])), 0)
        items, lanes = tampered_commit(
            cs, rng.randrange(1, n_heights + 1), chunk, rng)
        got = verifier.verify(items)
        others = rng.sample(range(len(items)), min(256, len(items)))
        h.check("verdicts_differing_from_openssl", sum(
            1 for i in set(lanes) | set(others)
            if bool(got[i]) != openssl_verify(*items[i])), 0)
        h.note("tampered_commit", lanes=len(lanes),
               in_tail_chunk=sum(1 for i in lanes if i >= chunk))

        valset, _commits = cs.decode()
        validators = cs.validators()
        differing = 0
        for name, block_id, height, votes in commit_cases(cs, chunk, rng):
            want = commitref.verify_commit(cs.chain_id, validators,
                                           block_id, height, votes)
            try:
                valset.verify_commit(
                    cs.chain_id, commits.program_block_id(block_id), height,
                    commits.program_commit(cs.addresses, block_id, votes))
                said = None
            except ValueError as e:
                said = str(e)
            h.note("commit_case", case=name, height=height,
                   reference=want or "accepted", program=said or "accepted")
            differing += (want is None) != (said is None)
        h.check("commit_verdicts_differing_from_reference", differing, 0)

    # the scalar path the source compares with: one commit's signatures
    # through OpenSSL one at a time, outside the window; a note
    items = cs.items(1)
    t0 = time.perf_counter()
    scalar_ok = sum(openssl_verify(*it) for it in items)
    h.note("scalar", scalar_ms_per_commit=1000.0 * (time.perf_counter() - t0),
           signatures=len(items), valid=scalar_ok)

    return Outcome(attempted=n_heights * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
