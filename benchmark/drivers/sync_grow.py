"""Driver `sync_grow`: a fresh full node fast-syncing a chain whose
validator set is still filling, from one instant in-process peer that
serves wire bytes.

The `sync_join` driver's passes (the `sync` driver's `fresh_reactor` and
`drive`, `sync_join`'s `synced` and `program_counts`, all imported) over
benchmark/growchain.py's chain: joins that grow the set by one, leaves
that shrink it by one, changes of stake, each a `val:` transaction
inside a block the node executes. Set-up builds the chain from the seed,
holds it to the configuration's counts, and syncs it `warm_passes`
times, untimed; the window is whole passes of a fresh node (empty
stores, a new KVStore app, a new BlockchainReactor over the process's
verifier) syncing all the blocks, decoding inside the pass.

`correct`, after the window, every limit 0: the chain is the one the
configuration states (joins, leaves, changes of stake, distinct keys,
the cap never passed, the final size, and the size of the set at every
height as the plain reference derives it); every block of every pass
applied; the last pass's store holds the builder's blocks; and against
benchmark/joinref.py's replay of the same wire bytes: the app hash after
every block, every stored header's `validators_hash`, the node's final
set, as many changes of set as the configuration states, and a seeded
sample of commits judged by OpenSSL under the reference's set for their
height. The verifier saw at least one signature a member of the set in
force a block applied. Four tampered copies, each synced by a fresh node
and replayed by the reference, each refused at its own height by both,
the peer punished: a forged precommit at a height whose commit has
another size than the set its window was collected under; at the first
height after a join, the joiner's precommit signed by another member's
key; at the first height after a leave, a commit that still carries the
leaver's slot; and a block with its join's `val:` transaction cut out,
refused one height up (by the reference for the header, which names the
set that transaction made; by the program for the commit above, which
has a slot more than the set it arrived at: both are noted). And one
copy that both must ACCEPT whole: above a change of size, one vote
claiming another member's address.
"""

from __future__ import annotations

import gc
import json
import random
import time

from benchmark import joinref, probe
from benchmark.chain import forge_precommit
from benchmark.drivers.sync import PEER_ID, drive, fresh_reactor
from benchmark.drivers.sync_join import program_counts, synced
from benchmark.growchain import (JOIN, LEAVE, GrowChain, address_rewritten,
                                 leaver_still_in_commit)
from benchmark.harness import Outcome
from benchmark.joinchain import STAKE, departed_signs_for_joiner
from benchmark.passes import Pass

RESIZED = "sync_resized_total"


def window_counts() -> dict:
    """`sync_join.program_counts`, and `tm_sync_resized_total` where the
    program counts it (a parent commit does not: its readers then find
    nothing)."""
    from tendermint_tpu import telemetry
    counts = program_counts()
    if RESIZED in telemetry.REGISTRY.names():
        counts[RESIZED] = float(telemetry.value(RESIZED) or 0.0)
    return counts


def reference_sizes(genesis_wire: bytes, wire) -> list:
    """How many validators are in force at height 1, 2, ... len(wire),
    by the plain reference's own rules."""
    _chain_id, vals = joinref.parse_genesis(genesis_wire)
    sizes = []
    for raw in wire:
        sizes.append(len(vals))
        vals = joinref.update(vals, joinref.txs_of(json.loads(raw)))
    return sizes


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.blockchain.reactor import VERIFY_WINDOW
    from tendermint_tpu.models.verifier import default_verifier

    p = h.params
    n_blocks, n_genesis = int(p["sync_blocks"]), int(p["genesis_validators"])
    cap, n_joins, n_leaves = (int(p["validator_cap"]), int(p["joins"]),
                              int(p["leaves"]))
    n_stake = int(p["stake_changes"])
    vwin = int(p["verify_window_blocks"])
    if not h.rehearsal and VERIFY_WINDOW != vwin:
        raise RuntimeError(
            f"the program's verify window is {VERIFY_WINDOW} blocks; the "
            f"configuration states {vwin}")
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/sync_grow")

    def build(**kw) -> GrowChain:
        return GrowChain(h.seed, n_blocks, n_genesis, cap, n_joins, n_leaves,
                         n_stake, int(p["txs_per_block"]), int(p["tx_bytes"]),
                         int(p["key_cycle_heights"]),
                         stake_scale=int(p["stake_scale"]), **kw)

    with h.spans.span("build_chain"):
        chain = build()
    wire, gen = chain.wire, chain.gen
    sizes = [chain.size_at[x] for x in range(1, n_blocks + 2)]
    needed = sum(sizes[:n_blocks])      # signatures a pass has to verify
    kinds = list(chain.change_at.values())
    h.note("chain", signatures=needed, sizes=sorted(set(sizes)),
           first_join=min(chain.joined_at), first_leave=min(chain.left_at),
           wire_bytes=sum(map(len, wire)))
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
        before = window_counts()
        try:
            passes, counters = h.timed_passes(timed, between, verifier)
        except BaseException:
            if "reactor" in held:
                held.pop("reactor").stop()
            raise
        counters.update(probe.delta(window_counts(), before))
        whole = sum(1 for q in passes if not q.failed)
        counters["join.needed_sigs"] = float(whole * needed)

        # ---- the chain is the configuration's
        keys = {v.pubkey for v in gen.validators} | {
            joiner for _member, joiner in chain.joined_at.values()}
        ref_sizes = reference_sizes(chain.genesis_wire, wire)
        h.check("chain_differing_from_the_configuration",
                abs(kinds.count(JOIN) - n_joins)
                + abs(kinds.count(LEAVE) - n_leaves)
                + abs(kinds.count(STAKE) - n_stake)
                + abs(len(keys) - (n_genesis + n_joins))
                + abs(sizes[-1] - (n_genesis + n_joins - n_leaves))
                + max(0, max(sizes) - cap) + int(min(chain.change_at) < 2)
                + int(ref_sizes != sizes), 0)

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
               why=ref.why[:120], final_size=len(ref.validators))
        moved = sum(1 for a, b in zip(ref.validators_hashes,
                                      ref.validators_hashes[1:]) if a != b)
        h.check("reference_short_of_the_chain", abs(n_blocks - ref.height)
                + abs(n_stake + n_joins + n_leaves - moved), 0)
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
        h.check("signatures_short_of_one_a_member_a_block",
                max(0, whole * sum(ref_sizes[:n_blocks])
                    - counters["verifier.sigs"]), 0)
        del last, metas, ref, carried

        # ---- tampered copies, program and reference side by side.
        # The first two windows are collected before anything applies,
        # so under the genesis set: a height there whose set has
        # another size (none only at a rehearsal's sizes, where the
        # first height above a change of size stands in)
        resized = [x for x in range(2, min(n_blocks, 2 * vwin - 2) + 1)
                   if sizes[x - 1] != n_genesis] or \
            [min(min(chain.joined_at), min(chain.left_at)) + 1]
        forged_at = rng.choice(resized)
        forged = wire[:min(n_blocks, -(-forged_at // vwin) * vwin) + 1]
        forged[forged_at] = forge_precommit(
            forged[forged_at], rng.randrange(sizes[forged_at - 1]))
        departed_at, departed = departed_signs_for_joiner(
            chain, min(chain.joined_at))
        leaver_at, leaver = leaver_still_in_commit(
            chain, min(chain.left_at))
        cut_block = rng.choice(sorted(chain.joined_at)[:8])
        with h.spans.span("build_cut_chain"):
            cut = build(cut_val_at=cut_block).wire
        claims_at = rng.choice(resized)
        claims = address_rewritten(
            chain, claims_at, *rng.sample(range(sizes[claims_at - 1]), 2))
        # (name, the height judged, wire, the reference's reason or
        # None for the copy that is accepted)
        cases = (
            ("forged_precommit", forged_at, forged, joinref.SIGNATURE),
            ("joiners_vote_signed_by_another_member", departed_at, departed,
             joinref.SIGNATURE),
            ("leavers_slot_still_in_the_commit", leaver_at, leaver,
             joinref.COMMIT),
            ("join_val_tx_cut", cut_block + 1, cut, joinref.VALIDATORS_HASH),
            ("vote_claims_another_members_address", claims_at, claims, None),
        )
        for name, at, twire, kind in cases:
            reactor, error = synced(gen, verifier, vwin, twire, h.spans)
            ref = joinref.replay(chain.genesis_wire, twire,
                                 check_signatures=lambda x, at=at: x >= at - 1)
            reached = reactor.state.last_block_height
            punished = {q for q, _ in reactor.switch.stopped} == {PEER_ID} \
                and PEER_ID not in reactor.pool.peers
            h.note("tampered", case=name, height=at, applied=reached,
                   blocks_refused=len(twire) - 1 - reached,
                   set_size=sizes[at - 1], genesis_size=n_genesis,
                   punished=punished, program=repr(error)[:120],
                   reference_applied=ref.height, reference=ref.why[:120],
                   refused_for=ref.kind)
            if kind is None:
                h.check(f"{name}_not_accepted_whole",
                        abs(reached - at) + abs(ref.height - at)
                        + int(ref.kind is not None) + int(punished)
                        + int(error is not None), 0)
            else:
                h.check(f"{name}_not_refused_at_its_height",
                        abs(reached - (at - 1)) + abs(ref.height - (at - 1))
                        + int(ref.kind != kind)
                        + int(not punished or error is not None), 0)
            del reactor

    return Outcome(attempted=n_blocks * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
