"""Driver `follow`: a light client certifying every header of a chain
whose validator set moves.

Set-up makes the chain from the seed (benchmark/churnchain.py: signed on
the device, held as wire bytes) and runs `warm_passes` whole untimed
passes: two, because one does not reach the state the timed passes run
in. A chunk with a key never sighted takes the fused kernel and stores
no rows (ops/ed25519._predecomp_rows), and with a key joining every 64
headers that is three chunks in four of a first pass: it leaves most
keys sighted and not resident, so the pass after it spends some 30
chunks on a decompress dispatch and a blocking fetch that no later pass
makes, and where a key joined in the chain's last eight headers (one
seed in eight) it sends the tail chunk to the predecompressed kernel at
a shape not compiled yet. From the second pass on every key is resident
and a pass is 51 dispatches of that kernel and nothing else. The
window is whole passes of ContinuousCertifier.advance_many over the
same FullCommits, a fresh certifier a pass, over objects decoded afresh
from the wire bytes between passes (a light client certifies what its
provider just sent; decoding is outside the timed pass, as in the
`certify` driver, and so is one full collection after it).

`correct`, after the window, every limit 0: no genuine pass refused;
every signature of the window verified, and on the device; as many
verifier dispatches a pass as the chain has windows (a window is
`certify_window_headers` headers whatever boundaries fall inside it);
after each pass every change of set crossed, the last height certified
and the trusted set's hash benchmark/literef.py's; `literef.follow`
over the whole chain agrees (structure, hashes and both tallies at every
height; OpenSSL on every change height, its predecessor and a seeded
sample of the rest); a 256-signature sample of the device's signatures
byte-equal to OpenSSL's; and four seeded tampered chains refused by the
program and by the reference at the same height for the same kind, with
trust standing one below: a flipped signature bit in a change header, a
forged header dressed in genuine signatures, a validators document that
does not hash to the header's `validators_hash`, and a set of fresh keys
that signs its own header with its full quorum.
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import literef, probe
from benchmark.churnchain import ChurnChain
from benchmark.harness import Outcome
from benchmark.kvref import openssl_signer
from benchmark.passes import Pass

# the program's refusals by what their messages say, in literef's kinds
_KINDS = (
    ("expects height", literef.HEIGHT),
    ("wrong chain id", literef.CHAIN_ID),
    ("validators_hash", literef.VALIDATORS_HASH),
    ("not for this header", literef.HEADER_HASH),
    ("invalid signature", literef.SIGNATURE),
    ("insufficient voting power", literef.QUORUM),
)


def kind_of(error) -> str:
    msg = str(error)
    if "valset transition" in msg:
        return literef.ENDORSEMENT_SIGNATURE if "invalid signature" in msg \
            else literef.ENDORSEMENT
    for needle, kind in _KINDS:
        if needle in msg:
            return kind
    return literef.COMMIT


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               ContinuousCertifier,
                                               default_window)
    from tendermint_tpu.models.verifier import default_verifier

    if not hasattr(ContinuousCertifier, "advance_many"):
        # before anything is built: a program whose light client takes
        # a moving set one header a call has no batch path to measure
        raise RuntimeError(
            "this program's ContinuousCertifier has no advance_many: it "
            "cannot certify a run of headers across validator-set changes")

    p = h.params
    n_headers, n_vals = int(p["lite_headers"]), int(p["validators"])
    n_stake, n_members = int(p["stake_changes"]), int(p["membership_changes"])
    window = int(p["certify_window_headers"])
    if not h.rehearsal and default_window(n_vals) != window:
        raise RuntimeError(
            f"the program's certify window for {n_vals} validators is "
            f"{default_window(n_vals)} headers; the configuration states "
            f"{window}")
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/follow")
    n_windows = -(-n_headers // window)
    n_changes = n_stake + n_members

    with h.spans.span("build_chain"):
        chain = ChurnChain(h.seed, n_headers, n_vals, n_stake, n_members,
                           stake_scale=int(p["stake_scale"]))
    n_sigs = chain.n_sigs
    verifier = default_verifier()
    held = {}

    def follow(trusted, fcs):
        """(the certifier after the run, the error that stopped it)."""
        cert = ContinuousCertifier(chain.chain_id, trusted)
        try:
            cert.advance_many(fcs, window=window)
        except CertificationError as e:
            return cert, e
        return cert, None

    with h.spans.span("warm_passes"):
        trusted, fcs = chain.decode()
        for _ in range(int(p["warm_passes"])):
            _cert, error = follow(trusted, fcs)
            if error is not None:
                raise RuntimeError(
                    f"a warm pass refused a genuine chain: {error}")
        del trusted, fcs, _cert
    h.settle()

    def between():
        held.clear()
        with h.spans.span("decode"):
            held["trusted"], held["fcs"] = chain.decode()
        # what was just decoded is old before the pass starts, so a full
        # collection over it cannot fall inside one pass and outside
        # another
        gc.collect()
        return held

    def timed(prepared) -> Pass:
        with h.spans.span("advance_many"):
            t0 = time.perf_counter()
            cert, error = follow(prepared["trusted"], prepared["fcs"])
            dt = time.perf_counter() - t0
        if error is not None:
            h.note("refused", error=str(error)[:200])
        return Pass(t0, dt, n_headers, n_headers - cert.certified_height,
                    extra={"end": (cert.certified_height, cert.updates,
                                   cert.validators.hash())})

    with probe.VerifierTap(verifier, h.spans, p.get("control")):
        passes, counters = h.timed_passes(timed, between, verifier)
        held.clear()
        gc.collect()

        # ---- what the window produced, against the plain reference
        h.check("genuine_headers_refused", sum(q.failed for q in passes), 0)
        h.check_signatures(counters, n_sigs * len(passes))
        h.check("dispatch_windows_off_a_pass", abs(
            counters["verifier.calls"] - n_windows * len(passes)), 0)

        with h.spans.span("reference"):
            sets = [literef.parse_validators(w) for w in chain.valsets_wire]
            plain = [literef.parse_full_commit(w, sets[chain.set_of[i]])
                     for i, w in enumerate(chain.wire)]
            rest = [x for x in range(1, n_headers + 1)
                    if x not in chain.change_at
                    and x + 1 not in chain.change_at]
            openssl_at = set(chain.change_at) | {
                x - 1 for x in chain.change_at} | set(rng.sample(
                    rest, min(int(p["openssl_sample_headers"]), len(rest))))
            t0 = time.perf_counter()
            ref = literef.follow(chain.chain_id, sets[0], plain,
                                 check_signatures=openssl_at.__contains__)
            h.note("reference", seconds=time.perf_counter() - t0,
                   openssl_heights=len(openssl_at), height=ref.height,
                   changes=ref.changes, refused_at=ref.refused_at,
                   refused_for=ref.kind, why=ref.why[:120])
        h.check("reference_short_of_the_chain",
                abs(n_headers - ref.height) + abs(n_changes - ref.changes), 0)
        end = (n_headers, n_changes, literef.validators_hash(ref.trusted))
        h.check("passes_ending_elsewhere_than_the_reference",
                sum(1 for q in passes if q.extra["end"] != end), 0)
        sample = range(0, n_sigs, max(1, n_sigs // 256))
        h.check("device_signatures_differing_from_openssl", sum(
            1 for i in sample
            if chain.sigs[i] != openssl_signer(
                chain.signed_by[i]).sign(chain.msgs[i])), 0)

        # ---- four tampered chains, program and reference side by side.
        # A chain is cut at the end of the tampered height's window, so
        # the program dispatches the shapes of the timed passes; the
        # document that does not hash is a window's first header, which
        # leaves nothing of that window to dispatch
        changes = sorted(x for x in chain.change_at if x > n_headers // 8)
        anywhere = range(max(2, n_headers // 8), n_headers + 1)
        first_of_a_window = range(window + 1, n_headers + 1, window)
        cases = (
            ("flipped_signature", rng.choice(changes), literef.SIGNATURE,
             lambda x, upto: chain.flipped_signature(
                 x, rng.randrange(n_vals), upto)),
            ("forged_header", rng.choice(anywhere), literef.SIGNATURE,
             chain.forged_header),
            ("wrong_validators", rng.choice(first_of_a_window),
             literef.VALIDATORS_HASH, chain.wrong_validators),
            ("hostile_transition", rng.choice(anywhere), literef.ENDORSEMENT,
             lambda x, upto: chain.hostile_transition(x, h.seed, upto)),
        )
        differing = 0
        for name, at, kind, tamper in cases:
            upto = min(n_headers, -(-at // window) * window)
            wire, valsets_wire, set_of = tamper(at, upto)
            cert, error = follow(*chain.decode(wire, valsets_wire, set_of))
            tampered = literef.parse_full_commit(
                wire[at - 1],
                literef.parse_validators(valsets_wire[set_of[at - 1]]))
            ref = literef.follow(
                chain.chain_id, sets[0], plain[:at - 1] + [tampered],
                check_signatures=lambda x, at=at: x >= at - 1)
            said = (getattr(error, "height", None),
                    kind_of(error) if error is not None else None,
                    cert.certified_height, cert.validators.hash())
            want = (ref.refused_at, ref.kind, ref.height,
                    literef.validators_hash(ref.trusted))
            h.note("tampered", case=name, height=at, refused_for=kind,
                   program=str(error)[:120], reference=ref.why[:120],
                   program_trusts=said[2], reference_trusts=want[2])
            differing += not (said == want and said[:3] == (at, kind, at - 1))
        h.check("tampered_chains_not_refused_as_the_reference_does",
                differing, 0)

    return Outcome(attempted=n_headers * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
