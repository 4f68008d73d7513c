"""Driver `sync`: a fresh node fast-syncing a chain from one instant
in-process peer that serves wire bytes.

Copied from bench_fastsync.sync_reactor / drive_sync. Set-up builds the
chain from the seed (wire bytes and each block's hash and app hash) and
syncs it once, untimed, so every shape is compiled, the
predecompression cache is in its steady state and the heap has its
steady size. The window is whole
passes: a fresh node (empty stores, a new KVStore app, a new
BlockchainReactor over the process's verifier) syncs all the blocks;
decoding the wire bytes is inside the pass, where a real node does it.

`correct`, after the window: every block of every pass applied; the
last pass's store holds the builder's blocks; the app hash after every
block equals kvref.PlainKV's replay of the transactions read from the
wire bytes with `json` alone; every commit signature verified on the
device; and a forged precommit inside one verify window stops a sync
exactly below it and punishes the peer.
"""

from __future__ import annotations

import gc
import json
import random
import time

from benchmark import probe
from benchmark.chain import ChainBuilder, forge_precommit
from benchmark.harness import Outcome
from benchmark.kvref import PlainKV
from benchmark.passes import Pass

PEER_ID = "bench-peer"


class _PunishedPeers:
    """The slice of a p2p Switch the fast-sync reactor punishes through
    (blockchain/reactor._stop_peer)."""

    def __init__(self):
        self.peers = self
        self.stopped = []

    def get(self, peer_id):
        return peer_id

    def stop_peer_for_error(self, peer, err):
        self.stopped.append((peer, str(err)))


def fresh_reactor(gen, verifier, verify_window: int):
    """A fresh node's fast-sync engine: empty stores, a KVStore app and
    a BlockchainReactor over `verifier`."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.abci.types import ValidatorUpdate
    from tendermint_tpu.blockchain import BlockchainReactor
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.storage import BlockStore, MemDB, StateStore

    state_store = StateStore(MemDB())
    state = state_store.load_or_genesis(gen)
    conns = AppConns(local_client_creator(KVStoreApp()))
    conns.consensus.init_chain(
        [ValidatorUpdate(v.pubkey, v.voting_power)
         for v in state.validators.validators], gen.chain_id)
    exec_ = BlockExecutor(state_store, conns.consensus, verifier=verifier)
    reactor = BlockchainReactor(state, exec_, BlockStore(MemDB()),
                                fast_sync=True, verify_window=verify_window)
    reactor.switch = _PunishedPeers()
    return reactor


def drive(reactor, wire, spans) -> float:
    """Sync `wire` (the last block only lends its LastCommit) through
    the reactor's window engine, fed by one instant peer that answers a
    request for height h with block h's wire bytes. Seconds it took.
    Ends early if the reactor drops the peer for a bad block."""
    from tendermint_tpu.types.block import Block

    def send_request(peer_id: str, height: int) -> bool:
        with spans.span("decode"):
            block = Block.from_bytes(wire[height - 1])
        reactor.pool.add_block(peer_id, block, len(wire[height - 1]))
        return True

    reactor.pool.send_request = send_request
    # one infinitely fast peer: the per-peer request cap of the
    # reference would clamp the verify window to 50
    reactor.pool.max_pending_per_peer = 1 << 20
    reactor._collect_window = spans.wrap("collect", reactor._collect_window)
    reactor._apply_window = spans.wrap("apply", reactor._apply_window)
    n_sync = len(wire) - 1
    reactor.pool.set_peer_height(PEER_ID, len(wire))
    t0 = time.perf_counter()
    reactor.pool.make_next_requests()
    while reactor.state.last_block_height < n_sync and \
            PEER_ID in reactor.pool.peers:
        if not reactor._sync_window():
            reactor.pool.make_next_requests()
    return time.perf_counter() - t0


def txs_of_wire(raw: bytes):
    """A block's transactions from its wire bytes (canonical JSON,
    bytes as hex), with the standard library alone."""
    return [bytes.fromhex(t) for t in json.loads(raw)["data"]["txs"]]


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.models.verifier import default_verifier

    p = h.params
    n_blocks, n_vals = int(p["sync_blocks"]), int(p["validators"])
    n_txs, tx_bytes = int(p["txs_per_block"]), int(p["tx_bytes"])
    vwin = int(p["verify_window_blocks"])
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/sync")

    with h.spans.span("build_chain"):
        builder = ChainBuilder(h.seed, n_vals, n_txs, tx_bytes,
                               int(p["key_cycle_heights"]))
        wire, expect = builder.build_wire(n_blocks)
        sentinel, _ = builder.build_wire(1, with_txs=False)
        wire += sentinel
        gen = builder.gen
        del builder
    verifier = default_verifier()

    with h.spans.span("warm_pass"):
        # a whole pass, not one verify window: measured on the chip, a
        # first full pass after a one-window warm-up takes 13 s where
        # the later ones take 10.5 (the process's heap grows to its
        # steady size in it)
        warm = fresh_reactor(gen, verifier, vwin)
        try:
            drive(warm, wire, h.spans)
        finally:
            warm.stop()
        if warm.state.last_block_height != n_blocks:
            raise RuntimeError(f"the warm pass applied "
                               f"{warm.state.last_block_height} of {n_blocks}")
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
        try:
            passes, counters = h.timed_passes(timed, between, verifier)
        except BaseException:
            if "reactor" in held:
                held.pop("reactor").stop()
            raise

        # ---- what the window produced, against the plain reference
        h.check("blocks_not_applied", sum(q.failed for q in passes), 0)
        last = held.pop("reactor")
        last.stop()
        metas = [last.block_store.load_block_meta(i + 1)
                 for i in range(n_blocks)]
        h.check("stored_blocks_differing", sum(
            1 for m, (block_hash, app_hash) in zip(metas, expect)
            if m is None or m.block_id.hash != block_hash or
            m.header.app_hash != app_hash), 0)
        t0 = time.perf_counter()
        ref = PlainKV()
        after = [ref.apply_block(txs_of_wire(raw)) for raw in wire[:n_blocks]]
        carried = [e[1] for e in expect[1:]] + [last.state.app_hash]
        h.check("app_hashes_differing_from_plain_reference",
                sum(1 for a, b in zip(after, carried) if a != b), 0)
        h.note("reference", seconds=time.perf_counter() - t0,
               keys=len(ref.store))
        del ref, after, last, metas
        h.check_signatures(counters, n_blocks * n_vals * len(passes))
        n_forged = min(vwin, n_blocks)
        forged_at = rng.randrange(max(2, n_forged // 16), n_forged)
        fwire = wire[:n_forged + 1]
        fwire[forged_at] = forge_precommit(fwire[forged_at],
                                           rng.randrange(n_vals))
        freactor = fresh_reactor(gen, verifier, vwin)
        try:
            drive(freactor, fwire, h.spans)
        except Exception as e:      # noqa: BLE001  any way out but a clean stop
            h.note("forged_sync_raised", error=repr(e)[:200])
        finally:
            freactor.stop()
        applied = freactor.state.last_block_height
        punished = {q for q, _ in freactor.switch.stopped} == {PEER_ID} and \
            PEER_ID not in freactor.pool.peers
        h.note("forged_precommit", commit_for_block=forged_at,
               applied=applied, punished=punished)
        h.check("forged_commit_not_stopped_at_its_height",
                abs(applied - (forged_at - 1)) + (0 if punished else 1), 0)
        del freactor

    return Outcome(attempted=n_blocks * len(passes),
                   failed=sum(q.failed for q in passes),
                   passes=passes, counters=counters)
