"""Lite-client chain certification bench (BASELINE.json config 5).

The reference's light client certifies headers one at a time — one
`ValidatorSet.VerifyCommit` (V scalar Ed25519 verifies) per header
(lite/static_certifier.go:57; lite/performance_test.go:10-105 measures
exactly this loop). Here a whole run of consecutive headers goes through
`lite.certify_chain`, which pools EVERY commit signature across the
chain into batched device dispatches.

Workload: N synthetic headers, each signed by V validators — N·V
signatures certified end-to-end (structural checks + quorum math on
host, signatures on device). Reported as headers/sec with the
scalar-OpenSSL baseline measured over the same per-header verify loop.

Standalone: `python bench_lite.py [n_headers] [n_vals]` prints one JSON
line. bench.py folds `run()` into its `extra` field for the driver.
"""

from __future__ import annotations

import json
import os
import sys
import time

from tendermint_tpu.utils import compile_cache

compile_cache.enable()  # before the first compile


from bench_util import fast_signer


def _signers(keys):
    return {k.pubkey.address: fast_signer(k.seed) for k in keys}


def build_chain(n_headers: int, n_vals: int, chain_id: str = "bench-lite"):
    """[FullCommit] for heights 1..n_headers, one constant valset."""
    from tendermint_tpu.lite.types import FullCommit, SignedHeader
    from tendermint_tpu.types import PrivKey
    from tendermint_tpu.types.block import (BlockID, Commit, Header,
                                            PartSetHeader)
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    keys = [PrivKey.generate((i + 1).to_bytes(32, "little"))
            for i in range(n_vals)]
    valset = ValidatorSet([Validator(k.pubkey.ed25519, 10) for k in keys])
    sign = _signers(keys)
    by_addr = {v.address: i for i, v in enumerate(valset.validators)}

    fcs = []
    for height in range(1, n_headers + 1):
        header = Header(chain_id=chain_id, height=height, time_ns=height,
                        validators_hash=valset.hash(),
                        app_hash=height.to_bytes(32, "big"))
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x22" * 32))
        precommits = [None] * n_vals
        for k in keys:
            idx = by_addr[k.pubkey.address]
            v = Vote(k.pubkey.address, idx, height, 0, height,
                     VoteType.PRECOMMIT, bid)
            v.signature = sign[k.pubkey.address](v.sign_bytes(chain_id))
            precommits[idx] = v
        fcs.append(FullCommit(
            SignedHeader(header, Commit(bid, precommits), bid), valset))
    return fcs, valset


def scalar_baseline_rate(fcs, chain_id: str, budget_s: float = 3.0):
    """Headers/sec for the reference execution model: one scalar Ed25519
    verify per precommit per header (lite/performance_test.go's loop),
    on the FASTEST scalar backend available (OpenSSL beats Go's
    x/crypto, so this is a conservative baseline)."""
    from bench_util import scalar_verify_one
    _v = scalar_verify_one()

    def verify(pub, sig, msg):
        assert _v(pub, msg, sig)

    n_done = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        fc = fcs[n_done % len(fcs)]
        pubs = {v.address: v.pubkey for v in fc.validators.validators}
        for pc in fc.signed_header.commit.precommits:
            if pc is not None:
                verify(pubs[pc.validator_address], pc.signature,
                       pc.sign_bytes(chain_id))
        n_done += 1
    return n_done / (time.perf_counter() - t0)


def run(n_headers: int = 2000, n_vals: int = 64,
        with_baseline: bool = True) -> dict:
    from tendermint_tpu.lite.certifier import certify_chain

    chain_id = "bench-lite"
    t0 = time.perf_counter()
    fcs, valset = build_chain(n_headers, n_vals)
    build_s = time.perf_counter() - t0

    # compile every kernel shape the measured certify will dispatch
    # (full chunks + padded tail) BEFORE the timed region
    from tendermint_tpu.models.verifier import default_verifier
    default_verifier().warmup(n_headers * n_vals)

    # best-of-3 (same policy as the headline and fast-sync arms;
    # whether a locally attached chip needs it is not measured)
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        certify_chain(chain_id, fcs, trusted=valset)
        dt = min(dt, time.perf_counter() - t0)
    rate = n_headers / dt

    out = {
        "headers_per_sec": round(rate, 1),
        "headers": n_headers, "vals_per_header": n_vals,
        "sig_verifies_per_sec": round(rate * n_vals, 1),
        "certify_s": round(dt, 3), "build_s": round(build_s, 1),
    }
    if with_baseline:
        base = scalar_baseline_rate(fcs, chain_id)
        out["scalar_headers_per_sec"] = round(base, 1)
        out["vs_baseline"] = round(rate / base, 2)
    return out


def run_streamed(n_headers: int = 1_000_000, n_vals: int = 64,
                 wave: int = 16384, deadline: float = None) -> dict:
    """Config 5 at FULL scale: 1M headers x 64 validators, streamed —
    build a wave (untimed: TPU batch signing via ops/ed25519.sign_batch,
    ~5-6us/signature end-to-end), certify it (timed), alternate. Memory
    stays bounded at one wave; sustained headers/s across all timed
    waves is the headline, per VERDICT r3 item 4.

    `deadline` (time.monotonic() timestamp): stop cleanly after the
    current wave once passed — the artifact then reports the achieved
    header count with scaled_to_budget=True instead of the driver
    SIGTERM-ing mid-arm and losing the whole result (VERDICT r4
    weak #1)."""
    from tendermint_tpu.lite.certifier import certify_chain
    from tendermint_tpu.lite.types import FullCommit, SignedHeader
    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.ops import ed25519 as ed
    from tendermint_tpu.types import PrivKey
    from tendermint_tpu.types.block import (BlockID, Commit, Header,
                                            PartSetHeader)
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    chain_id = "bench-lite"
    # Signature disk cache: the wave build is UNTIMED setup (the metric
    # is certify headers/s), but 64M device signatures cost ~6 min of
    # wall clock the driver budget can't spare — so waves persist their
    # signatures once per box, keyed by every parameter that shapes
    # them. certify_chain re-verifies every cached signature, so a
    # corrupt cache fails the arm loudly rather than passing silently.
    # TM_BENCH_NO_SIGCACHE=1 disables (fields report cache use either
    # way).
    cache_dir = None
    if not os.environ.get("TM_BENCH_NO_SIGCACHE"):
        cache_dir = os.path.join(os.path.dirname(os.path.abspath(
            __file__)), ".bench_sigcache")
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:
            cache_dir = None
    cache_hits = 0
    seeds = [(i + 1).to_bytes(32, "little") for i in range(n_vals)]
    keys = [PrivKey.generate(s) for s in seeds]
    valset = ValidatorSet([Validator(k.pubkey.ed25519, 10) for k in keys])
    order = {k.pubkey.address: i for i, k in enumerate(keys)}
    idx_of = [order[v.address] for v in valset.validators]
    vals = valset.validators
    vhash = valset.hash()

    default_verifier().warmup(wave * n_vals)
    # the final PARTIAL wave ends with a short certify window whose
    # batch shape nothing above compiles — warm it too, or its JIT
    # compile lands inside the last timed wave
    from tendermint_tpu.lite.certifier import default_window
    tail_h = (n_headers % wave) % default_window(n_vals)
    if tail_h:
        default_verifier().warmup(tail_h * n_vals)
    t_all = time.perf_counter()
    build_s = 0.0
    warm_s = 0.0
    timed_s = 0.0
    best_wave = 0.0
    wave_rates = []

    def build_wave(b_done: int):
        """Build one wave starting at height b_done+1; returns
        (fcs, seconds, cache_hit). Pure host work on the cached-sig
        path, so it runs on a helper thread UNDER the next wave's
        certify — certify's device fetches release the GIL, and the
        build fills those gaps (1-core pipelining; with ~40%% host
        occupancy during certify the build is nearly free)."""
        tb = time.perf_counter()
        n_w = min(wave, n_headers - b_done)
        heights = range(b_done + 1, b_done + n_w + 1)
        headers, bids = [], []
        for h in heights:
            header = Header(chain_id=chain_id, height=h, time_ns=h,
                            validators_hash=vhash,
                            app_hash=h.to_bytes(32, "big"))
            bid = BlockID(header.hash(), PartSetHeader(1, b"\x22" * 32))
            headers.append(header)
            bids.append(bid)
        wave_idx = b_done // wave
        cpath = None
        blob = None
        if cache_dir is not None:
            cpath = os.path.join(
                cache_dir, f"{chain_id}-v{n_vals}-w{wave}"
                           f"-i{wave_idx}-n{n_w}.sig")
            try:
                if os.path.getsize(cpath) == n_w * n_vals * 64:
                    with open(cpath, "rb") as f:
                        blob = f.read()
            except OSError:
                pass
        resolver = None
        if blob is None:
            # sign-bytes only exist on the signing path — every
            # validator signs the SAME canonical bytes per header
            # (v0.16 sign bytes carry no validator identity; one
            # timestamp); a cache hit skips the n_w encodes entirely
            msgs = [Vote(vals[0].address, 0, h, 0, h,
                         VoteType.PRECOMMIT,
                         bids[h - (b_done + 1)]).sign_bytes(chain_id)
                    for h in heights]
            sig_seeds = [seeds[idx_of[j]]
                         for _ in range(n_w) for j in range(n_vals)]
            sig_msgs = [m for m in msgs for _ in range(n_vals)]
            # dispatch signing, then build the vote/commit objects
            # WHILE the device computes R = r*B — signatures attach at
            # resolve
            resolver = ed.sign_batch_async(sig_seeds, sig_msgs)
        fcs = []
        all_votes = []
        vote_new = Vote.__new__
        addrs = [v.address for v in vals]
        for i, h in enumerate(heights):
            bid = bids[i]
            # slim construction: 1M dataclass __init__ calls per wave
            # cost more than the certify host plane; a prototype dict
            # + __dict__.update builds identical instances
            proto = {"height": h, "round": 0, "timestamp_ns": h,
                     "type": VoteType.PRECOMMIT, "block_id": bid,
                     "signature": b"", "validator_index": 0,
                     "validator_address": b""}
            precommits = [None] * n_vals
            for j in range(n_vals):
                v = vote_new(Vote)
                d = v.__dict__
                d.update(proto)
                d["validator_address"] = addrs[j]
                d["validator_index"] = j
                precommits[j] = v
                all_votes.append(v)
            fcs.append(FullCommit(
                SignedHeader(headers[i], Commit(bid, precommits), bid),
                valset))
        if blob is not None:
            for i, v in enumerate(all_votes):
                v.signature = blob[64 * i:64 * (i + 1)]
        else:
            sigs = resolver()
            for v, sig in zip(all_votes, sigs):
                v.signature = sig
            if cpath is not None:
                try:  # atomic publish; a failed write just skips cache
                    tmp = cpath + f".{os.getpid()}.tmp"
                    with open(tmp, "wb") as f:
                        f.write(b"".join(sigs))
                    os.replace(tmp, cpath)
                except OSError:
                    pass
        return fcs, time.perf_counter() - tb, blob is not None

    def wave_cached(b_done: int) -> bool:
        if cache_dir is None:
            return False
        n_w = min(wave, n_headers - b_done)
        cpath = os.path.join(
            cache_dir, f"{chain_id}-v{n_vals}-w{wave}"
                       f"-i{b_done // wave}-n{n_w}.sig")
        try:
            return os.path.getsize(cpath) == n_w * n_vals * 64
        except OSError:
            return False

    from concurrent.futures import ThreadPoolExecutor
    pool = ThreadPoolExecutor(max_workers=1,
                              thread_name_prefix="lite-build")
    done = 0
    fut = pool.submit(build_wave, 0)
    try:
        while done < n_headers:
            fcs, b_s, hit = fut.result()
            fut = None
            build_s += b_s
            cache_hits += int(hit)
            n_w = len(fcs)
            if deadline is not None and done > 0 and \
                    time.monotonic() >= deadline:
                break  # past deadline: don't certify the prebuilt wave
            if done + n_w < n_headers and wave_cached(done + n_w):
                # pipeline ONLY cache-hit builds (pure host work that
                # fills certify's GIL-free device waits); a cache-miss
                # build dispatches TPU signing, which must not compete
                # with the timed certify — it runs sequentially below
                fut = pool.submit(build_wave, done + n_w)
            if done == 0:
                # one untimed mini-certify first: the verifier's
                # warmup() compiles the FULL kernel shapes, but
                # certify's steady state runs the predecompressed
                # variant (engages on the 2nd sighting of this
                # valset's padded pubkey batch) — its ~40s Mosaic
                # compile must not land in wave 1's timed run
                tw = time.perf_counter()
                certify_chain(chain_id, fcs[:1024], trusted=valset)
                warm_s = time.perf_counter() - tw
            tw = time.perf_counter()
            certify_chain(chain_id, fcs, trusted=valset)
            dt = time.perf_counter() - tw
            timed_s += dt
            best_wave = max(best_wave, n_w / dt)
            wave_rates.append(n_w / dt)
            done += n_w
            if fut is None and done < n_headers:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                fut = pool.submit(build_wave, done)  # sequential: wait
                # (miss path; certify of this wave already finished)
    finally:
        pool.shutdown(wait=True)
    wave_rates.sort()
    return {
        "headers_per_sec": round(done / timed_s, 1),
        "best_wave_headers_per_sec": round(best_wave, 1),
        # the median wave of a long run, beside its mean and best
        "median_wave_headers_per_sec": round(
            wave_rates[len(wave_rates) // 2], 1),
        "headers": done, "target_headers": n_headers,
        "scaled_to_budget": done < n_headers,
        "vals_per_header": n_vals,
        "waves": (done + wave - 1) // wave, "wave_headers": wave,
        "sig_verifies_per_sec": round(done * n_vals / timed_s, 1),
        "sig_cache_waves": cache_hits,
        "certify_s": round(timed_s, 3), "build_s": round(build_s, 1),
        "warm_s": round(warm_s, 1),
        "total_wall_s": round(time.perf_counter() - t_all, 1),
    }


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--streamed":
        args = [int(a) for a in sys.argv[2:]]
        r = run_streamed(*args)
        print(json.dumps({
            "metric": "lite_chain_certify_1m",
            "value": r["headers_per_sec"],
            "unit": "headers/sec", "vs_baseline": 0.0, "extra": r,
        }))
        return 0
    n_headers = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
    n_vals = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    r = run(n_headers, n_vals)
    print(json.dumps({
        "metric": "lite_chain_certify",
        "value": r["headers_per_sec"],
        "unit": "headers/sec",
        "vs_baseline": r.get("vs_baseline", 0.0),
        "extra": r,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
