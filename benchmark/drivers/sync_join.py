"""Driver `sync_join`: a fresh full node fast-syncing a chain whose
validator set moves through EndBlock, from one instant in-process peer
that serves wire bytes.

The `sync` driver's passes (its `fresh_reactor` and `drive`, imported)
over benchmark/joinchain.py's chain: set-up
builds the chain from the seed and syncs it `warm_passes` times,
untimed (two: a first pass meets every key for the first time, and the
harness refuses a compile in the window); the window is whole passes of
a fresh node (empty stores, a new KVStore app, a new BlockchainReactor
over the process's verifier) syncing all the blocks, decoding inside the
pass. The validator set reaches the node only as `val:` transactions
inside the blocks it executes.

`correct`, after the window, every limit 0: every block of every pass
applied; the last pass's store holds the builder's blocks; and against
benchmark/joinref.py's replay of the same wire bytes: the app hash after
every block, every stored header's `validators_hash`, the node's final
set, as many changes of set as the configuration states, and a seeded
sample of commits judged by OpenSSL under the reference's set for their
height. The verifier saw at least one signature a validator a block
applied. Three tampered copies, each synced by a fresh node and replayed
by the reference, each refused at its own height by both: a forged
precommit at a height whose set is no longer the one its window was
collected with (the peer punished); at the first height after a join,
the joiner's precommit signed by the key that left (the peer punished);
and a block with its `val:` transaction cut out, refused one height up,
where the header names the set that transaction made.
"""

from __future__ import annotations

import gc
import random
import time

from benchmark import joinref, probe
from benchmark.chain import forge_precommit
from benchmark.drivers.sync import PEER_ID, drive, fresh_reactor
from benchmark.harness import Outcome
from benchmark.joinchain import (MEMBERSHIP, STAKE, JoinChain,
                                 departed_signs_for_joiner)
from benchmark.passes import Pass

# the program's counters of the window engine, by label
_FAMILIES = (("sync_commits_total", ("batched", "reverified")),
             ("sync_lanes_total", ("used", "discarded")))


def program_counts() -> dict:
    """`tm_sync_commits_total{how}` and `tm_sync_lanes_total{how}` as
    they stand (they count while telemetry is on: a traced run), keyed
    "<family>.<how>"; nothing where the program has neither family (a
    parent commit, which the driver measures under these files). One
    without the other is a counter renamed or removed since: that
    raises, so its readers cannot fall silent unnoticed."""
    from tendermint_tpu import telemetry
    names = telemetry.REGISTRY.names()
    missing = [family for family, _hows in _FAMILIES if family not in names]
    if len(missing) == len(_FAMILIES):
        return {}
    if missing:
        raise RuntimeError(f"the program counts no tm_{missing[0]}")
    return {f"{family}.{how}":
            float(telemetry.value(family, {"how": how}) or 0.0)
            for family, hows in _FAMILIES for how in hows}


def synced(gen, verifier, vwin: int, wire, spans):
    """A fresh node synced over `wire`, stopped: (the reactor, what the
    sync raised or None)."""
    reactor = fresh_reactor(gen, verifier, vwin)
    error = None
    try:
        drive(reactor, wire, spans)
    except Exception as e:      # noqa: BLE001  any way out but a clean stop
        error = e
    finally:
        reactor.stop()
    return reactor, error


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain.reactor import VERIFY_WINDOW
    from tendermint_tpu.models.verifier import default_verifier

    p = h.params
    n_blocks, n_vals = int(p["sync_blocks"]), int(p["validators"])
    n_stake, n_members = int(p["stake_changes"]), int(p["membership_changes"])
    vwin = int(p["verify_window_blocks"])
    if not h.rehearsal and VERIFY_WINDOW != vwin:
        raise RuntimeError(
            f"the program's verify window is {VERIFY_WINDOW} blocks; the "
            f"configuration states {vwin}")
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/sync_join")

    def build(**kw) -> JoinChain:
        return JoinChain(h.seed, n_blocks, n_vals, n_stake, n_members,
                         int(p["txs_per_block"]), int(p["tx_bytes"]),
                         int(p["key_cycle_heights"]),
                         stake_scale=int(p["stake_scale"]), **kw)

    with h.spans.span("build_chain"):
        chain = build()
    wire, gen = chain.wire, chain.gen
    verifier = default_verifier()

    with h.spans.span("warm_passes"):
        for _ in range(int(p["warm_passes"])):
            c0, t0 = probe.counters(verifier), time.perf_counter()
            warm, error = synced(gen, verifier, vwin, wire, h.spans)
            if warm.state.last_block_height != n_blocks:
                raise RuntimeError(
                    f"a warm pass applied {warm.state.last_block_height} "
                    f"of {n_blocks}: {error!r}")
            # which kernels a pass still reaches: the timed passes must
            # reach none that these have not compiled
            h.note("warm_pass", seconds=time.perf_counter() - t0, kernels={
                k[len("kernel."):]: v for k, v in probe.delta(
                    probe.counters(verifier), c0).items()
                if k.startswith("kernel.") and v})
            del warm
            gc.collect()
    h.spans.by_name.clear()
    h.settle()

    held = {}

    def between():
        old = held.pop("reactor", None)
        if old is not None:
            old.stop()
        del old
        gc.collect()
        return None

    def timed(_prepared) -> Pass:
        reactor = held["reactor"] = fresh_reactor(gen, verifier, vwin)
        with h.spans.span("sync_pass"):
            t0 = time.perf_counter()
            dt = drive(reactor, wire, h.spans)
        return Pass(t0, dt, n_blocks,
                    n_blocks - reactor.state.last_block_height)

    with probe.VerifierTap(verifier, h.spans, p.get("control")):
        before = program_counts()
        try:
            passes, counters = h.timed_passes(timed, between, verifier)
        except BaseException:
            if "reactor" in held:
                held.pop("reactor").stop()
            raise
        counters.update(probe.delta(program_counts(), before))
        applied = sum(q.work - q.failed for q in passes)
        counters["join.needed_sigs"] = float(applied * n_vals)

        # ---- what the window produced, against the plain reference
        h.check("blocks_not_applied", sum(q.failed for q in passes), 0)
        last = held.pop("reactor")
        last.stop()
        metas = [last.block_store.load_block_meta(i + 1)
                 for i in range(n_blocks)]
        h.check("stored_blocks_differing", sum(
            1 for m, (block_hash, app_hash) in zip(metas, chain.expect)
            if m is None or m.block_id.hash != block_hash or
            m.header.app_hash != app_hash), 0)
        sample = set(rng.sample(range(1, n_blocks + 1), min(
            int(p["openssl_sample_commits"]), n_blocks)))
        t0 = time.perf_counter()
        ref = joinref.replay(chain.genesis_wire, wire,
                             check_signatures=sample.__contains__)
        h.note("reference", seconds=time.perf_counter() - t0,
               openssl_commits=len(sample), height=ref.height,
               refused_at=ref.refused_at, refused_for=ref.kind,
               why=ref.why[:120])
        moved = sum(1 for a, b in zip(ref.validators_hashes,
                                      ref.validators_hashes[1:]) if a != b)
        h.check("reference_short_of_the_chain", abs(n_blocks - ref.height)
                + abs(n_stake + n_members - moved), 0)
        carried = [m.header.app_hash for m in metas[1:] if m is not None] \
            + [last.state.app_hash]
        h.check("app_hashes_differing_from_plain_reference",
                abs(len(carried) - len(ref.app_hashes)) + sum(
                    1 for a, b in zip(ref.app_hashes, carried) if a != b), 0)
        h.check("validators_hashes_differing_from_plain_reference", sum(
            1 for m, want in zip(metas, ref.validators_hashes)
            if m is None or m.header.validators_hash != want), 0)
        final = [(v.pubkey, v.voting_power)
                 for v in last.state.validators.validators]
        h.check("final_set_differing_from_plain_reference",
                int(final != ref.validators) + int(
                    last.state.validators.hash()
                    != ref.validators_hashes[-1]), 0)
        h.check("signatures_short_of_one_a_validator_a_block",
                max(0, applied * n_vals - counters["verifier.sigs"]), 0)
        del last, metas, ref, carried

        # ---- three tampered copies, program and reference side by side
        changes = sorted(chain.change_at)
        first = changes[0]
        lo = max(vwin // 4, first + 1)
        forged_at = rng.randrange(lo, vwin) if lo < vwin else first + 1
        forged = wire[:min(n_blocks, -(-forged_at // vwin) * vwin) + 1]
        forged[forged_at] = forge_precommit(forged[forged_at],
                                            rng.randrange(n_vals))
        join_height = min(x for x, kind in chain.change_at.items()
                          if kind == MEMBERSHIP)
        departed_at, departed = departed_signs_for_joiner(chain, join_height)
        cut_block = rng.choice(sorted(
            x for x, kind in chain.change_at.items() if kind == STAKE)[:8])
        with h.spans.span("build_cut_chain"):
            cut = build(cut_val_at=cut_block).wire
        cases = (
            ("forged_precommit", forged_at, forged, joinref.SIGNATURE, True),
            ("departed_key_signs_for_joiner", departed_at, departed,
             joinref.SIGNATURE, True),
            ("val_tx_cut", cut_block + 1, cut, joinref.VALIDATORS_HASH,
             False),
        )
        for name, at, twire, kind, punishes in cases:
            reactor, error = synced(gen, verifier, vwin, twire, h.spans)
            ref = joinref.replay(chain.genesis_wire, twire,
                                 check_signatures=lambda x, at=at: x >= at - 1)
            reached = reactor.state.last_block_height
            punished = {q for q, _ in reactor.switch.stopped} == {PEER_ID} \
                and PEER_ID not in reactor.pool.peers
            said = "validators_hash" in str(error) if not punishes \
                else punished and error is None
            h.note("tampered", case=name, height=at, applied=reached,
                   blocks_refused=len(twire) - 1 - reached,
                   changes_below=sum(1 for x in changes if x < at),
                   punished=punished, program=repr(error)[:120],
                   reference_applied=ref.height, reference=ref.why[:120],
                   refused_for=ref.kind)
            h.check(f"{name}_not_refused_at_its_height",
                    abs(reached - (at - 1)) + abs(ref.height - (at - 1))
                    + int(ref.kind != kind) + int(not said), 0)
            del reactor

    return Outcome(attempted=n_blocks * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
