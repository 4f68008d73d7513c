"""4-validator testnet commit-rate bench (BASELINE.json config 1).

The reference's config-1 baseline is a 4-validator local testnet running
the kvstore ABCI app with 1000-tx blocks. Here: four in-process
ConsensusStates over a full-mesh relay (the same wiring the consensus
test nets use), MockTicker-driven so the measured rate is the ENGINE's
throughput — proposal build + part gossip + vote verify + apply — not
the configured wall-clock timeouts. Each proposer reaps 1000 txs per
block from its mempool.

Standalone: `python bench_testnet.py [n_blocks] [n_vals] [n_txs]`
prints one JSON line. bench.py folds `run()` into `extra` for the
driver.
"""

from __future__ import annotations

import json
import sys
import time

from tendermint_tpu.utils import compile_cache

compile_cache.enable()  # before the first compile

from tendermint_tpu.utils import knobs  # noqa: E402 (post-cache-setup)


class _BenchMempool:
    """Endless reap: always has the next block's txs ready. `pending`
    carries real injected txs (the churn driver's val: txs) ahead of
    the fabricated filler — removed once seen committed, so every
    node's copy drains in step like a real mempool."""

    def __init__(self, n_txs: int):
        self.n_txs = n_txs
        self._next = 0
        self.committed = 0
        self.pending = []

    def lock(self):
        pass

    def unlock(self):
        pass

    def size(self):
        return self.n_txs

    def inject(self, tx: bytes):
        if tx not in self.pending:
            self.pending.append(tx)

    def reap(self, max_txs: int):
        base = self._next
        k = self.n_txs if max_txs < 0 else min(self.n_txs, max_txs)
        out = list(self.pending[:k])
        return out + [b"bench/k%d=v%d" % (base + i, i)
                      for i in range(k - len(out))]

    def update(self, height, txs):
        self._next += len(txs)
        self.committed += len(txs)
        if self.pending:
            committed = set(txs)
            self.pending = [t for t in self.pending
                            if t not in committed]

    def txs_available(self):
        return True


def run(n_blocks: int = 30, n_vals: int = 4, n_txs: int = 1000,
        churn_every: int = 0, churn_standby: int = 2) -> dict:
    """`churn_every` > 0 turns on the validator-churn driver: every
    that-many committed heights one `val:` tx (join a standby key /
    stake-change it / leave it, cycling) is injected into every
    node's mempool — the valset rotates through REAL EndBlock
    validator_updates while the bench measures. Standby keys run no
    ConsensusState (a joined-but-absent validator costs rounds when
    it wins proposer — that cost is part of what churn measures)."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.abci.types import ValidatorUpdate
    from tendermint_tpu.config import test_config as make_test_config
    from tendermint_tpu.consensus import ConsensusState, MockTicker
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.storage import BlockStore, MemDB, StateStore
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
    from tendermint_tpu.types.priv_validator import LocalSigner, PrivValidator

    keys = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(n_vals)]
    standby = [PrivKey.generate(bytes([200, i + 1]) * 16)
               for i in range(churn_standby if churn_every else 0)]
    gen = GenesisDoc(chain_id="bench-net", genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519, 10)
                                 for k in keys])

    nodes = []
    for k in keys:
        conns = AppConns(local_client_creator(KVStoreApp()))
        state_store = StateStore(MemDB())
        block_store = BlockStore(MemDB())
        state = state_store.load_or_genesis(gen)
        conns.consensus.init_chain(
            [ValidatorUpdate(v.pubkey, v.voting_power)
             for v in state.validators.validators], gen.chain_id)
        mp = _BenchMempool(n_txs)
        exec_ = BlockExecutor(state_store, conns.consensus, mempool=mp)
        cs = ConsensusState(
            make_test_config().consensus, state, exec_, block_store,
            mempool=mp, priv_validator=PrivValidator(LocalSigner(k)),
            ticker_factory=MockTicker)
        nodes.append(cs)

    # full-mesh relay of proposal/part/vote broadcasts
    for i, src in enumerate(nodes):
        def relay(msg, i=i):
            for j, dst in enumerate(nodes):
                if j != i and msg["type"] in ("proposal", "block_part",
                                              "vote"):
                    dst.submit(dict(msg), peer_id=f"node{i}")
        src.broadcast_hooks.append(relay)

    def fire_all():
        n = 0
        for node in nodes:
            if node.ticker.fire_next() is not None:
                n += 1
        return n

    for node in nodes:
        node.start()

    # churn driver: deterministic op cycle over the standby keys,
    # advanced by committed height, injected into EVERY mempool (the
    # next proposer includes it; absolute powers make a duplicate
    # inclusion idempotent)
    churn_state = {"next_h": churn_every + 1, "op_i": 0, "ops": 0,
                   "joined": []}

    def drive_churn():
        if not churn_every or not standby:
            return
        h = min(n.state.last_block_height for n in nodes)
        if h < churn_state["next_h"]:
            return
        churn_state["next_h"] = h + churn_every
        kind = ("join", "stake", "leave")[churn_state["op_i"] % 3]
        churn_state["op_i"] += 1
        tx = None
        if kind == "join":
            free = [k for k in standby
                    if k not in churn_state["joined"]]
            if free:
                churn_state["joined"].append(free[0])
                tx = b"val:%s/10" % free[0].pubkey.ed25519.hex().encode()
        elif kind == "stake" and churn_state["joined"]:
            tx = b"val:%s/15" % churn_state["joined"][0] \
                .pubkey.ed25519.hex().encode()
        elif kind == "leave" and churn_state["joined"]:
            k = churn_state["joined"].pop(0)
            tx = b"val:%s/0" % k.pubkey.ed25519.hex().encode()
        if tx is not None:
            churn_state["ops"] += 1
            for node in nodes:
                node.mempool.inject(tx)

    def run_to(height, max_ticks):
        for _ in range(max_ticks):
            if all(n.state.last_block_height >= height for n in nodes):
                return True
            drive_churn()
            fire_all()
        return all(n.state.last_block_height >= height for n in nodes)

    # warmup: first blocks pay kernel compiles + app-hash settling
    assert run_to(2, 400), "testnet warmup stalled"

    h0 = min(n.state.last_block_height for n in nodes)
    tx0 = nodes[0].mempool.committed
    t0 = time.perf_counter()
    target = h0 + n_blocks
    assert run_to(target, 400 * n_blocks), "testnet bench stalled"
    dt = time.perf_counter() - t0
    blocks = min(n.state.last_block_height for n in nodes) - h0
    txs = nodes[0].mempool.committed - tx0

    final_vals = nodes[0].state.validators
    out = {
        "blocks_per_sec": round(blocks / dt, 2),
        "txs_per_sec": round(txs / dt, 1),
        "blocks": blocks, "n_vals": n_vals, "txs_per_block": n_txs,
        "seconds": round(dt, 3),
    }
    if churn_every:
        out["churn"] = {
            "ops_injected": churn_state["ops"],
            "final_valset_size": len(final_vals),
            "final_total_power": final_vals.total_voting_power(),
            "last_height_validators_changed":
                nodes[0].state.last_height_validators_changed,
        }
    for node in nodes:
        node.stop()
    return out


def _scrape_p2p_metrics(client) -> dict:
    """Pull the frame-plane instruments from one node's /metrics
    exposition (the nodes are separate OS processes — telemetry lives
    behind their RPC, exactly where a production scrape would read)."""
    import re
    text = client.call("metrics")["exposition"]
    vals = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = re.match(r'^(tm_p2p_[a-z_]+?)(\{[^}]*\})? ([0-9.e+-]+)$', line)
        if not m:
            continue
        name, labels, v = m.group(1), m.group(2) or "", float(m.group(3))
        vals[name + labels] = vals.get(name + labels, 0.0) + v
    out = {}
    fsum = vals.get('tm_p2p_frames_per_burst_sum{direction="send"}', 0.0)
    fcnt = vals.get('tm_p2p_frames_per_burst_count{direction="send"}', 0.0)
    if fcnt:
        out["mean_frames_per_send_burst"] = round(fsum / fcnt, 2)
    sealed = vals.get("tm_p2p_frames_sealed_total", 0.0)
    seal_s = vals.get("tm_p2p_seal_seconds_sum", 0.0)
    if sealed:
        out["seal_us_per_frame"] = round(seal_s / sealed * 1e6, 2)
        out["frames_sealed"] = int(sealed)
    opened = vals.get("tm_p2p_frames_opened_total", 0.0)
    open_s = vals.get("tm_p2p_open_seconds_sum", 0.0)
    if opened:
        out["open_us_per_frame"] = round(open_s / opened * 1e6, 2)
    return out


def _scrape_pipeline_metrics(client) -> dict:
    """tm_pipeline_* / tm_partset_* from one node's /metrics — per-stage
    seconds, overlap ratio and precompute outcomes, so the bench arms
    can attribute the win to specific pipeline stages."""
    import re
    text = client.call("metrics")["exposition"]
    sums, counts, out = {}, {}, {}
    for line in text.splitlines():
        m = re.match(r'^(tm_(?:pipeline|partset)_[a-z_]+?)'
                     r'(\{[^}]*\})? ([0-9.e+-]+)$', line)
        if not m:
            continue
        name, labels, v = m.group(1), m.group(2) or "", float(m.group(3))
        if name.endswith("_sum"):
            sums[name[:-4] + labels] = v
        elif name.endswith("_count"):
            counts[name[:-6] + labels] = v
        elif name.endswith("_total"):
            out[name + labels] = int(v)
    for key, s in sums.items():
        n = counts.get(key, 0)
        if n:
            out[key + "_mean"] = round(s / n, 6)
            out[key + "_count"] = int(n)
    return out


def _scrape_compact_metrics(clients) -> dict:
    """tm_compact_* / tm_voteagg_* summed across EVERY node — one
    node's sends are another's reconstructions, so per-node numbers
    understate the plane. Adds the two derived ratios the trend gate
    tracks: reconstruct hit rate (hit+fetched over all attempts) and
    mean votes per aggregate."""
    import re
    out: dict = {}
    for c in clients:
        text = c.call("metrics")["exposition"]
        for line in text.splitlines():
            m = re.match(r'^(tm_(?:compact|voteagg)_[a-z_]+?)'
                         r'(\{[^}]*\})? ([0-9.e+-]+)$', line)
            if not m:
                continue
            key = m.group(1) + (m.group(2) or "")
            out[key] = out.get(key, 0.0) + float(m.group(3))
    if not out:
        return {}
    out = {k: (int(v) if float(v).is_integer() else v)
           for k, v in out.items()}
    hit = out.get('tm_compact_reconstruct_total{outcome="hit"}', 0)
    fetched = out.get(
        'tm_compact_reconstruct_total{outcome="fetched"}', 0)
    fallback = out.get(
        'tm_compact_reconstruct_total{outcome="fallback"}', 0)
    attempts = hit + fetched + fallback
    if attempts:
        out["compact_reconstruct_hit_rate"] = round(
            (hit + fetched) / attempts, 4)
    batch_sum = out.get("tm_voteagg_batch_votes_sum", 0)
    batch_n = out.get("tm_voteagg_batch_votes_count", 0)
    if batch_n:
        out["voteagg_mean_batch"] = round(batch_sum / batch_n, 2)
    return out


def _chain_parity(clients, part_size: int = 65536) -> dict:
    """Bit-identity audit of a finished arm's chain, recomputed SERIALLY
    in this (parent) process:

    - every block's bytes re-encode to the stored header hash
      (Block.from_obj -> to_bytes -> from_bytes round trip),
    - every block's header.app_hash equals a fresh serial KVStore
      replay of the txs so far (the AppHash chain is bit-identical to
      what the non-pipelined executor would produce),
    - the committed part-set roots equal both the serial Python split
      and the native one-call builder, recomputed from the block bytes,
    - all validators report the same height/app-hash frontier.

    Raises AssertionError on any mismatch; returns a summary dict."""
    from tendermint_tpu import native
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.ops import merkle
    from tendermint_tpu.types.block import Block

    h = min(c.call("status")["latest_block_height"] for c in clients)
    first = 1
    app = KVStoreApp()
    app_hash = b""
    partset_checks = 0
    for height in range(first, h + 1):
        r = clients[0].call("block", height=height)
        meta, blk_obj = r["block_meta"], r["block"]
        block = Block.from_obj(blk_obj)
        if height > 1:
            assert block.header.app_hash == app_hash, (
                f"height {height}: header.app_hash diverged from "
                f"serial replay")
        data = block.to_bytes()
        rt = Block.from_bytes(data)
        assert rt.hash().hex() == meta["block_id"]["hash"], (
            f"height {height}: block bytes do not re-encode to the "
            f"stored header hash")
        want_root = meta["block_id"]["parts"]["hash"]
        chunks = [data[i:i + part_size]
                  for i in range(0, len(data), part_size)] or [b""]
        serial_root, _ = merkle.tree_proofs_host(chunks)
        assert serial_root.hex() == want_root, (
            f"height {height}: serial part-set root != committed root")
        built = native.partset_build(data, part_size)
        if built is not None:
            assert built[0].hex() == want_root, (
                f"height {height}: native part-set root != committed")
        partset_checks += 1
        for tx in block.data.txs:
            app.deliver_tx(tx)
        app_hash = app.commit()
    frontiers = set()
    for c in clients:
        s = c.call("status")
        if s["latest_block_height"] >= h:
            b = c.call("block", height=h)
            frontiers.add((b["block_meta"]["block_id"]["hash"],
                           b["block"]["header"]["app_hash"]))
    assert len(frontiers) == 1, f"validators disagree at {h}: {frontiers}"
    return {"blocks_verified": h - first + 1,
            "app_hash_chain_bit_identical": True,
            "block_bytes_bit_identical": True,
            "partset_roots_bit_identical": partset_checks,
            "validators_agree_at": h}


def _scrape_chaos_metrics(client) -> dict:
    """tm_chaos_faults_injected_total by kind from one node's /metrics
    — evidence the chaos plane actually fired in a TM_TPU_CHAOS run."""
    import re
    text = client.call("metrics")["exposition"]
    out = {}
    for line in text.splitlines():
        m = re.match(r'^tm_chaos_faults_injected_total\{kind="([a-z_]+)"\}'
                     r' ([0-9.e+-]+)$', line)
        if m:
            out[m.group(1)] = int(float(m.group(2)))
    return out


def _scrape_ban_metrics(client) -> dict:
    """tm_p2p_bans/unbans/peer_errors/accept_shed/handshake_failures
    from one node's /metrics — the hostile-peer defense witness."""
    import re
    text = client.call("metrics")["exposition"]
    out = {}
    for line in text.splitlines():
        m = re.match(
            r'^(tm_p2p_(?:bans|unbans|peer_errors|accept_shed|'
            r'handshake_failures|frame_error_disconnects)_total|'
            r'tm_p2p_banned_peers)(\{[^}]*\})? ([0-9.e+-]+)$', line)
        if m:
            out[m.group(1) + (m.group(2) or "")] = int(float(m.group(3)))
    return out


def run_socket(n_vals: int = 4, n_txs_target: int = 1000,
               duration_s: float = 25.0, burst: str = "",
               chaos: str = "", pipeline: str = "",
               parity: bool = False, trace: str = "",
               profile: str = "", reactor: str = "",
               wire_chaos: dict = None, wire_seed: int = 0,
               hostile: tuple = (), liveness_bound_s: float = 30.0,
               child_env: dict = None, p2p_cfg: dict = None,
               slo: str = "", slo_sample: float = 0.0,
               tx_subscribers: int = 0) -> dict:
    """Config 1 over REAL sockets: n_vals separate OS processes
    (`cli node --p2p`), real TCP P2P + secret connections + local ABCI,
    txs injected over HTTP RPC by background spammer threads; commit
    rate and committed tx/s measured from block metas over a wall-clock
    window. The analogue of the reference's dockerized
    test/p2p/atomic_broadcast testnet, recorded as a NUMBER (the
    in-process `run()` above isolates the engine; this arm includes
    every socket, handshake, and gossip cost). On a 1-core bench host
    the four nodes and the spammers share one core — the figure is a
    floor, not the engine ceiling."""
    import json as _json
    import os
    import socket as _socket
    import subprocess
    import tempfile
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))

    from bench_util import free_port_block, node_child_env
    env = node_child_env(repo)
    if burst:  # per-arm override for the frame-plane A/B (bench.py
        #        --p2p-json); "" inherits whatever the caller exported
        env["TM_TPU_P2P_BURST"] = burst
    if chaos:  # chaos-plane link faults for every node (e.g.
        #        "drop=0.02,delay=0.05,seed=7"); "" inherits caller env
        env["TM_TPU_CHAOS"] = chaos
    if pipeline:  # per-arm hot-path pipeline A/B (bench.py --p2p-json);
        #          "" inherits whatever the caller exported
        env["TM_TPU_PIPELINE"] = pipeline
    if trace:  # causal tracing plane for every node (bench.py
        #       --trace-json); "" inherits whatever the caller exported
        env["TM_TPU_TRACE"] = trace
    if profile:  # sampling profiler A/B for every node (bench.py
        #         --profile-json); "" inherits the caller env
        env["TM_TPU_PROF"] = profile
    if reactor:  # async reactor core A/B (bench.py --p2p-json):
        #         loop = one event loop per node, threads = the
        #         per-connection thread plane; "" inherits caller env
        env["TM_TPU_REACTOR"] = reactor
    if slo:  # tx-lifecycle SLO plane A/B for every node (bench.py
        #     --slo-json); "" inherits whatever the caller exported
        env["TM_TPU_SLO"] = slo
        if slo_sample > 0:
            env["TM_TPU_SLO_SAMPLE"] = str(slo_sample)
    if child_env:  # per-run node knobs (bench.py --wirechaos-json uses
        #           this to shorten ban windows so the unban shows up
        #           inside the measured window)
        env.update(child_env)

    net = tempfile.mkdtemp(prefix="bench-socknet-")
    base = free_port_block(2 * n_vals)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--n", str(n_vals), "--output", net, "--base-port", str(base),
         "--chain-id", "bench-socknet"],
        env=env, check=True, capture_output=True, timeout=120)
    for i in range(n_vals):
        cfg_path = os.path.join(net, f"node{i}", "config", "config.json")
        cfg = _json.load(open(cfg_path))
        cfg["consensus"].update({
            "timeout_propose": 400, "timeout_propose_delta": 100,
            "timeout_prevote": 200, "timeout_prevote_delta": 100,
            "timeout_precommit": 200, "timeout_precommit_delta": 100,
            "timeout_commit": 100,
            "max_block_size_txs": n_txs_target})
        # a few blocks of backlog: enough to keep every block at
        # the 1000-tx reap cap, small enough that per-commit
        # recheck + mempool-WAL rewrite stay O(small)
        cfg["mempool"] = dict(cfg.get("mempool", {}), size=4000)
        if p2p_cfg:
            # per-run p2p overrides (the wirechaos bench shortens the
            # handshake deadline so slow-loris disconnects land inside
            # the measured window)
            cfg["p2p"] = dict(cfg.get("p2p", {}), **p2p_cfg)
        _json.dump(cfg, open(cfg_path, "w"))

    # wire-level chaos (ISSUE 13): route every directed p2p link
    # through the seeded TCP fault proxy — node i's persistent_peers
    # entry for node j points at proxy port (i, j), which forwards to
    # j's real listener injecting the schedule's faults. PEX is
    # disabled so no conn can discover a direct (unproxied) address.
    proxy = wire_sched = wire_monitor = None
    wire_t0 = None
    hostile_threads: list = []
    hostile_reports: list = []
    slo_subs: list = []
    if wire_chaos is not None:
        from tendermint_tpu.chaos import wire as wire_mod
        proxy, wire_sched = wire_mod.proxy_for_testnet(
            wire_chaos, wire_seed, n_vals, lambda j: base + 2 * j)
        for i in range(n_vals):
            cfg_path = os.path.join(net, f"node{i}", "config",
                                    "config.json")
            cfg = _json.load(open(cfg_path))
            peers = []
            for entry in cfg["p2p"]["persistent_peers"].split(","):
                if not entry:
                    continue
                pid, hostport = entry.split("@", 1)
                port = int(hostport.rsplit(":", 1)[1])
                j = (port - base) // 2
                peers.append(f"{pid}@127.0.0.1:{proxy.ports[(i, j)]}")
            cfg["p2p"]["persistent_peers"] = ",".join(peers)
            cfg["p2p"]["pex"] = False
            _json.dump(cfg, open(cfg_path, "w"))
        proxy.start()

    procs, logs = [], []
    cleanup_ok = [False]
    n_spammers = 2
    stop = threading.Event()
    sent = [0] * n_spammers
    try:
        for i in range(n_vals):
            log = open(os.path.join(net, f"node{i}.log"), "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.cli",
                 "--home", os.path.join(net, f"node{i}"),
                 "node", "--p2p", "--no-fast-sync",
                 "--rpc-laddr", f"tcp://127.0.0.1:{base + 2 * i + 1}",
                 "--max-seconds", "600"],
                env=env, stdout=log, stderr=subprocess.STDOUT))

        from tendermint_tpu.rpc.client import (JSONRPCClient,
                                               RPCClientError)
        clients = [JSONRPCClient(f"http://127.0.0.1:{base + 2 * i + 1}")
                   for i in range(n_vals)]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                if all(c.call("status")["latest_block_height"] >= 2
                       for c in clients):
                    break
            except (OSError, RPCClientError):
                pass  # still booting; the liveness check below decides
            if any(p.poll() is not None for p in procs):
                raise RuntimeError("socket-testnet node died during boot")
            time.sleep(0.5)
        else:
            raise RuntimeError("socket testnet made no progress")

        def spam(tid):
            # tm-bench shape, batched: fire-and-forget broadcast_tx_batch
            # casts of 128 txs over one persistent websocket. Per-tx
            # casts cost a server round trip each and capped injection
            # at ~500 tx/s on this shared core; the pipelined commit
            # path drains thousands per second, so the spammers must
            # keep up for blocks to stay at the 1000-tx reap cap.
            from tendermint_tpu.rpc.client import WSClient
            ws = None
            i = 0
            while not stop.is_set():
                try:
                    if ws is None:
                        ws = WSClient("127.0.0.1",
                                      base + 2 * (tid % n_vals) + 1)
                    for _ in range(4):
                        ws.cast("broadcast_tx_batch",
                                txs=[(b"s%d.%d=v" % (tid, i + k)).hex()
                                     for k in range(128)])
                        i += 128
                    sent[tid] = i  # per-thread slot: no racy +=
                    # periodic sync point: don't outrun the server,
                    # and back off while the backlog is deep enough
                    while not stop.is_set() and ws.call(
                            "num_unconfirmed_txs",
                            timeout=30.0)["n_txs"] > 3000:
                        time.sleep(0.05)
                except Exception:
                    if ws is not None:
                        try:
                            ws.close()
                        except OSError:
                            pass  # already torn down server-side
                        ws = None
                    time.sleep(0.2)

        spammers = [threading.Thread(target=spam, args=(t,), daemon=True)
                    for t in range(n_spammers)]
        for t in spammers:
            t.start()

        slo_on = bool(slo) and slo.lower() not in knobs.FALSY
        if tx_subscribers > 0:
            # Tx-event WS subscribers per node: the delivery-stage
            # witness for an SLO run (each node's deliver stamp is a
            # real fan-out socket write), attached INDEPENDENTLY of
            # the SLO knob so an off-vs-on A/B carries identical
            # event-delivery load on both arms; a bench-side thread
            # empties the client queues so nothing backlogs
            import queue as _queue
            from tendermint_tpu.rpc.client import WSClient
            for i in range(n_vals):
                for _ in range(tx_subscribers):
                    ws = WSClient("127.0.0.1", base + 2 * i + 1)
                    ws.subscribe("tm.event = 'Tx'")
                    slo_subs.append(ws)

            def drain_events():
                while not stop.is_set():
                    drained = False
                    for ws in slo_subs:
                        try:
                            for _ in range(4096):
                                ws.events.get_nowait()
                                drained = True
                        except _queue.Empty:
                            pass
                    if not drained:
                        time.sleep(0.05)

            threading.Thread(target=drain_events, daemon=True,
                             name="bench-slo-drain").start()
        # pre-fill: HTTP injection (~500 tx/s on this shared core) is
        # slower than commit throughput, so build a mempool BACKLOG
        # first — the measured window then reaps config-1-shaped
        # (1000-tx) blocks, the sustained-load profile of the
        # reference's atomic_broadcast testnet
        def check_alive():
            dead = [i for i, p in enumerate(procs)
                    if p.poll() is not None]
            if dead:
                raise RuntimeError(f"socket-testnet nodes died: {dead}")

        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            check_alive()
            try:
                if clients[0].call("num_unconfirmed_txs")[
                        "n_txs"] >= 2500:
                    break
            except (OSError, RPCClientError):
                pass  # node busy/restarting; check_alive decides
            time.sleep(1.0)

        if proxy is not None:
            # faults begin WITH the measured window (boot + prefill ran
            # on a clean wire); the monitor sees exactly what an
            # operator's scrape would
            from tendermint_tpu.chaos import wire as wire_mod
            wire_t0 = proxy.arm()
            wire_monitor = wire_mod.SocketInvariantMonitor(
                [f"http://127.0.0.1:{base + 2 * i + 1}"
                 for i in range(n_vals)])
            wire_monitor.start()
        for script in hostile:
            # hostile peers aim at node0's REAL p2p listener — the
            # defenses under test live in the victim, not the proxy
            from tendermint_tpu.chaos import hostile as hostile_mod

            def run_script(s=script):
                kw = {}
                if s == "garbage_after_auth":
                    kw = {"rounds": 12, "retry_gap_s": 1.2,
                          "budget_s": duration_s + 10}
                elif s == "flood":
                    kw = {"count": 48, "hold_s": 2.0}
                elif s == "slow_handshake":
                    kw = {"byte_interval_s": 0.5,
                          "budget_s": min(20.0, duration_s)}
                elif s == "handshake_stall":
                    kw = {"budget_s": min(20.0, duration_s)}
                try:
                    hostile_reports.append(hostile_mod.run_hostile(
                        s, "127.0.0.1", base, network="bench-socknet",
                        channels=[], **kw))
                except Exception as e:
                    hostile_reports.append({"script": s,
                                            "error": repr(e)})
            t = threading.Thread(target=run_script, daemon=True,
                                 name=f"hostile-{script}")
            t.start()
            hostile_threads.append(t)

        h0 = clients[0].call("status")["latest_block_height"]
        t0 = time.perf_counter()
        end_at = time.monotonic() + duration_s
        while time.monotonic() < end_at:
            check_alive()
            time.sleep(1.0)
        h1 = clients[0].call("status")["latest_block_height"]
        dt = time.perf_counter() - t0
        stop.set()
        wire_report = {}
        if proxy is not None:
            for t in hostile_threads:
                t.join(timeout=20.0)
            # grace so the monitor can observe post-heal progress for
            # late episodes, then judge
            time.sleep(3.0)
            ends = []
            for ep in wire_sched.episodes():
                end_t = wire_t0 + ep["end"] * wire_sched.step_ms / 1e3
                if end_t <= time.monotonic():
                    ends.append((ep["kind"], end_t))
            wire_monitor.stop()
            bans = {}
            for c in clients:
                try:
                    for k, v in _scrape_ban_metrics(c).items():
                        bans[k] = bans.get(k, 0) + v
                except (OSError, RPCClientError) as e:
                    print(f"[bench] ban scrape failed: {e!r}",
                          file=sys.stderr)
            wire_report = {
                "spec": wire_sched.spec, "seed": wire_sched.seed,
                "step_ms": wire_sched.step_ms,
                "plan": wire_sched.plan,
                "plan_sha256": wire_sched.plan_digest(),
                "faults_applied": wire_sched.applied_counts(),
                "monitor": wire_monitor.finalize(
                    ends, liveness_bound_s=liveness_bound_s),
                "hostile": hostile_reports,
                "ban_metrics": bans,
            }
        try:
            p2p_metrics = _scrape_p2p_metrics(clients[0])
        except Exception:
            p2p_metrics = {}
        try:
            pipeline_metrics = _scrape_pipeline_metrics(clients[0])
        except Exception:
            pipeline_metrics = {}
        try:
            compact_metrics = _scrape_compact_metrics(clients)
        except Exception:
            compact_metrics = {}
        timelines = []
        if trace:
            # every node's span ring BEFORE teardown: the measured
            # window's heights plus all link spans (clock alignment);
            # bench.py merges them into the cluster timeline
            for c in clients:
                try:
                    timelines.append(c.call(
                        "dump_height_timeline",
                        min_height=h0 + 1, max_height=h1))
                except (OSError, RPCClientError) as e:
                    print(f"[bench] timeline fetch failed: {e!r}",
                          file=sys.stderr)
        profiles = []
        if profile and profile.lower() not in ("off", "0", "false"):
            # every node's sampling-profiler table BEFORE teardown:
            # collapsed stacks + per-subsystem busy/wait sample counts
            # (bench.py merges them into the cluster profile)
            for c in clients:
                try:
                    profiles.append(c.call("debug_profile",
                                           action="dump"))
                except (OSError, RPCClientError) as e:
                    print(f"[bench] profile fetch failed: {e!r}",
                          file=sys.stderr)
        slo_reports = []
        if slo_on:
            # every node's SLO snapshot WITH mergeable sketches before
            # teardown (bench.py / scripts/slo_report.py merge them)
            for c in clients:
                try:
                    slo_reports.append(c.call("slo", sketches=True))
                except (OSError, RPCClientError) as e:
                    print(f"[bench] slo fetch failed: {e!r}",
                          file=sys.stderr)
        parity_report = {}
        if parity:
            # bit-identity audit BEFORE teardown: serial replay of the
            # whole chain in this process (AssertionError on mismatch)
            parity_report = _chain_parity(clients)
        chaos_metrics = {}
        if chaos or (knobs.knob_raw("TM_TPU_CHAOS") or "off") \
                .lower() not in knobs.FALSY:
            try:
                chaos_metrics = _scrape_chaos_metrics(clients[0])
            except Exception:
                pass
        txs = 0
        # the blockchain route caps at 20 metas per call: page through
        lo = h0 + 1
        while lo <= h1:
            hi = min(lo + 19, h1)
            metas = clients[0].call("blockchain", min_height=lo,
                                    max_height=hi)["block_metas"]
            txs += sum(m["header"]["num_txs"] for m in metas)
            lo = hi + 1
        cleanup_ok[0] = True
        return {
            "blocks_per_sec": round((h1 - h0) / dt, 2),
            "txs_per_sec": round(txs / dt, 1),
            "blocks": h1 - h0,
            "avg_txs_per_block": round(txs / max(1, h1 - h0), 1),
            "n_vals": n_vals, "seconds": round(dt, 1),
            "txs_injected": sum(sent),
            "transport": "tcp sockets, 4 OS processes, secret conns",
            "burst": burst or "default",
            "pipeline": pipeline or "default",
            "reactor": reactor or "default",
            "p2p": p2p_metrics,
            **({"pipeline_metrics": pipeline_metrics}
               if pipeline_metrics else {}),
            **({"compact_metrics": compact_metrics}
               if compact_metrics else {}),
            **({"parity": parity_report} if parity_report else {}),
            **({"chaos": chaos, "chaos_faults": chaos_metrics}
               if chaos_metrics else {}),
            **({"wire": wire_report} if wire_report else {}),
            **({"timelines": timelines} if timelines else {}),
            **({"profiles": profiles} if profiles else {}),
            **({"slo_reports": slo_reports} if slo_reports else {}),
        }
    except BaseException:
        # keep the net tree and surface log tails: the node logs are
        # the only diagnostics for a boot/run failure
        for i, log in enumerate(logs):
            try:
                log.flush()
                with open(log.name) as f:
                    tail = f.read()[-1200:]
                print(f"--- socknet node{i} log tail ---\n{tail}",
                      file=sys.stderr)
            except OSError:
                pass
        raise
    finally:
        stop.set()
        for ws in slo_subs:
            try:
                ws.close()
            except OSError:
                pass
        if wire_monitor is not None:
            wire_monitor.stop()
        if proxy is not None:
            proxy.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()
        if cleanup_ok[0]:
            # only after every node process is down and logs are
            # closed: rmtree must not race live writers
            import shutil
            shutil.rmtree(net, ignore_errors=True)


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--socket":
        r = run_socket()
        print(json.dumps({
            "metric": "testnet_socket_commit_rate",
            "value": r["blocks_per_sec"], "unit": "blocks/sec",
            "vs_baseline": 0.0, "extra": r,
        }))
        return 0
    n_blocks = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    n_vals = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    n_txs = int(sys.argv[3]) if len(sys.argv) > 3 else 1000
    r = run(n_blocks, n_vals, n_txs)
    print(json.dumps({
        "metric": "testnet_commit_rate",
        "value": r["blocks_per_sec"],
        "unit": "blocks/sec",
        "vs_baseline": 0.0,
        "extra": r,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
