"""Driver `certify`: a light client certifying a run of headers.

Set-up makes the signed header chain from the seed (signed on the
device, held as wire bytes) and runs one whole untimed pass, so every
shape is compiled and the predecompression cache is in its steady
`hit` state. The window is whole passes of lite.certify_chain over the
same headers, each over objects decoded afresh from the wire bytes
between passes (a light client certifies what its provider just sent;
the API takes objects, so decoding is outside the timed pass).

`correct`, after the window: no genuine pass rejected; every signature
of the window verified on the device; a 256-signature sample of the
device's signatures byte-equal to OpenSSL's; the timed verifier's
verdicts on one full certify window with seeded tampered lanes equal to
OpenSSL's, lane by lane; and a forged header inside the full chain
rejected at its own height.
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import probe
from benchmark.chain import LiteChain
from benchmark.harness import Outcome
from benchmark.kvref import openssl_signer, openssl_verify
from benchmark.passes import Pass


def tampered_window(chain: LiteChain, n_headers: int, rng) -> tuple:
    """(items, tampered lanes): the first `n_headers` headers' triples
    with seeded lanes broken four ways, spread over the whole batch."""
    nv = chain.n_vals
    items = [(chain.pubkeys[j], chain.msgs[i], chain.sigs[i * nv + j])
             for i in range(n_headers) for j in range(nv)]
    lanes = sorted(rng.sample(range(len(items)), min(40, len(items) // 4)))
    for k, lane in enumerate(lanes):
        pub, msg, sig = items[lane]
        if k % 4 == 0:
            sig = bytes([sig[0] ^ 1]) + sig[1:]             # R
        elif k % 4 == 1:
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]    # s
        elif k % 4 == 2:
            msg = msg + b"x"
        else:
            pub = chain.pubkeys[(lane + 1) % nv]
        items[lane] = (pub, msg, sig)
    return items, lanes


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain,
                                               default_window)
    from tendermint_tpu.models.verifier import default_verifier

    p = h.params
    n_headers, n_vals = int(p["lite_headers"]), int(p["validators"])
    window_headers = int(p["certify_window_headers"])
    if not h.rehearsal and default_window(n_vals) != window_headers:
        raise RuntimeError(
            f"the program's certify window for {n_vals} validators is "
            f"{default_window(n_vals)} headers; the configuration states "
            f"{window_headers}")
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/certify")
    n_sigs = n_headers * n_vals

    with h.spans.span("build_chain"):
        chain = LiteChain(h.seed, n_headers, n_vals)
    verifier = default_verifier()
    held = {}

    def certify(fcs, valset) -> int:
        """0, or how many headers were not certified."""
        try:
            certify_chain(chain.chain_id, fcs, trusted=valset,
                          window=window_headers)
        except CertificationError as e:
            h.note("rejected", error=str(e)[:200])
            return len(fcs)
        return 0

    with h.spans.span("warm_pass"):
        valset, fcs = chain.decode()
        if certify(fcs, valset):
            raise RuntimeError("the warm pass rejected a genuine chain")
        del valset, fcs
    h.settle()

    def between():
        held.clear()
        gc.collect()
        with h.spans.span("decode"):
            held["valset"], held["fcs"] = chain.decode()
        return held

    def timed(prepared) -> Pass:
        with h.spans.span("certify_chain"):
            t0 = time.perf_counter()
            bad = certify(prepared["fcs"], prepared["valset"])
            dt = time.perf_counter() - t0
        return Pass(t0, dt, n_headers, bad)

    with probe.VerifierTap(verifier, h.spans, p.get("control")):
        passes, counters = h.timed_passes(timed, between, verifier)
        held.clear()
        gc.collect()

        # ---- what the window produced, against the plain reference
        h.check("genuine_headers_rejected", sum(q.failed for q in passes), 0)
        h.check_signatures(counters, n_sigs * len(passes))
        sample = range(0, n_sigs, max(1, n_sigs // 256))
        keys = [openssl_signer(s) for s in chain.seeds]
        h.check("device_signatures_differing_from_openssl", sum(
            1 for i in sample
            if chain.sigs[i] != keys[i % n_vals].sign(chain.msgs[i // n_vals])
        ), 0)
        items, lanes = tampered_window(
            chain, min(window_headers, n_headers), rng)
        got = verifier.verify(items)
        others = rng.sample(range(len(items)), min(256, len(items)))
        h.check("verdicts_differing_from_openssl", sum(
            1 for i in set(lanes) | set(others)
            if bool(got[i]) != openssl_verify(*items[i])), 0)
        forged_at = rng.randrange(max(2, n_headers // 8), n_headers + 1)
        wire = list(chain.wire)
        wire[forged_at - 1] = chain.forged_header(forged_at)
        valset, fcs = chain.decode(wire)
        try:
            certify_chain(chain.chain_id, fcs, trusted=valset,
                          window=window_headers)
            where = "certified"
        except CertificationError as e:
            where = str(e)
        h.note("forged_header", height=forged_at, outcome=where[:120])
        h.check("forged_header_not_rejected_at_its_height",
                0 if where.startswith(f"height {forged_at}:") else 1, 0)

    return Outcome(attempted=n_headers * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
