"""Node — assembles stores, app conns, handshake, WAL, consensus, the
reactor stack and the p2p switch (node/node.go:121-353).

With `with_p2p=True` the node runs the full networking stack: mempool /
evidence / blockchain (fast-sync) / consensus reactors + optional PEX on
an encrypted switch, listening on config.p2p.laddr and dialing seeds and
persistent peers. Without it, the node is a self-contained single-process
validator (the in-process test/tooling mode)."""

from __future__ import annotations

import os
from typing import Optional

from tendermint_tpu.abci.proxy import AppConns, local_client_creator
from tendermint_tpu.config import Config
from tendermint_tpu.consensus.replay import Handshaker, catchup_replay
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.ticker import TimeoutTicker
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.storage import WAL, BlockStore, StateStore, open_db
from tendermint_tpu.types import GenesisDoc, PrivValidatorFile
from tendermint_tpu.types.events import EventBus


def _parse_laddr(laddr: str) -> tuple:
    """tcp://host:port -> (host, port)."""
    s = laddr.split("://", 1)[-1]
    host, _, port = s.rpartition(":")
    return host or "0.0.0.0", int(port)


class Node:
    def __init__(self, config: Config, gen_doc: GenesisDoc,
                 priv_validator=None, app=None, client_creator=None,
                 mempool=None, evidence_pool=None, in_memory=False,
                 with_p2p=False, fast_sync=False, with_rpc=False,
                 wal_readonly=False, loop=None, node_key=None):
        from tendermint_tpu.utils.log import get_logger
        # logging is configured once at the CLI entry point; constructing
        # a Node (tests build several in-process) must not reconfigure
        # the process-global handler/levels. The chain id rides as a
        # logger FIELD, not a process-global bind — a shard plane runs
        # many chains in one process and their lines must stay
        # distinguishable (ISSUE 15 value-scoping).
        self.logger = get_logger("node", chain=gen_doc.chain_id)
        self.config = config
        self.gen_doc = gen_doc

        # telemetry wiring BEFORE any instrumented subsystem runs (the
        # handshake below already drives the verifier); env
        # TM_TPU_TELEMETRY wins over the config knob inside configure()
        from tendermint_tpu import telemetry
        telemetry.configure(
            enabled=getattr(config.base, "telemetry", True),
            namespace=getattr(config.base, "telemetry_namespace", "tm"))

        # p2p burst frame plane knobs (env TM_TPU_P2P_BURST wins inside
        # resolve(); connections snapshot these at creation time)
        from tendermint_tpu.p2p.conn import burst as _burst
        _burst.configure(
            mode=getattr(config.base, "p2p_burst", "auto"),
            max_packets=getattr(config.base, "p2p_burst_max", 0))

        # chaos plane knobs (env TM_TPU_CHAOS wins inside resolve();
        # "off" keeps every hot path on the existing code byte-for-byte)
        from tendermint_tpu import chaos as _chaos
        _chaos.configure(
            mode=getattr(config.base, "chaos", "off"),
            seed=getattr(config.base, "chaos_seed", 0))

        # pipelined block hot path (env TM_TPU_PIPELINE wins inside
        # resolve(); "off" restores the serial per-height code)
        from tendermint_tpu import pipeline as _pipeline
        _pipeline.configure(mode=getattr(config.base, "pipeline", "auto"))

        # compact consensus gossip (env TM_TPU_COMPACT / TM_TPU_VOTE_AGG
        # win inside the resolvers; both off = legacy wire byte-for-byte)
        from tendermint_tpu.consensus import compact as _compact
        _compact.configure(
            compact_mode=getattr(config.base, "compact", "auto"),
            voteagg_mode=getattr(config.base, "vote_agg", "auto"))

        # async reactor core (env TM_TPU_REACTOR wins inside resolve();
        # "threads" restores the per-connection thread plane exactly).
        # The ReactorLoop itself is created lazily below, only when a
        # p2p switch or RPC listener actually needs one.
        from tendermint_tpu.p2p.conn import loop as _loop_cfg
        _loop_cfg.configure(mode=getattr(config.base, "reactor", "auto"))
        # `loop=` injects a SHARED ReactorLoop (the shard plane runs N
        # nodes + one RPC front door on one selector); a node only
        # stops a loop it created itself.
        self.loop = loop
        self._owns_loop = loop is None

        # causal tracing plane (env TM_TPU_TRACE wins inside enabled();
        # off = untraced wire bytes + zero span recording). The node id
        # is refined to the p2p identity in _build_p2p.
        from tendermint_tpu.telemetry import causal as _causal
        _causal.configure(mode=getattr(config.base, "trace", "off"))
        if not _causal.node():
            _causal.set_node(getattr(config.base, "moniker", "") or
                             f"pid{os.getpid()}")
        self._stall_detector = None

        # runtime introspection plane (env wins inside each resolve):
        # sampling profiler + queue observatory, both process-global —
        # several in-process nodes share one sampler and one catalog
        from tendermint_tpu.telemetry import profile as _profile
        from tendermint_tpu.telemetry import queues as _queues
        _profile.configure(mode=getattr(config.base, "prof", "off"),
                           hz=getattr(config.base, "prof_hz", 0.0))
        _queues.configure(mode=getattr(config.base, "queue_watch", "on"))

        # tx-lifecycle SLO plane (env TM_TPU_SLO/_SLO_SAMPLE win inside
        # the resolvers; off = one cached flag check per entry point).
        # Process-global like the profiler: in-process testnets share
        # one tracker and stage stamps are first-wins idempotent.
        from tendermint_tpu.telemetry import slo as _slo
        _slo.configure(mode=getattr(config.base, "slo", "off"),
                       sample=getattr(config.base, "slo_sample", None))

        def db_path(name):
            if in_memory:
                return None
            p = config.path(config.base.db_dir)
            os.makedirs(p, exist_ok=True)
            return os.path.join(p, name + ".db")

        self.block_store = BlockStore(open_db(db_path("blockstore")))
        self.state_store = StateStore(open_db(db_path("state")))

        # recovery plane knobs (env > config > default; all-zero/off =
        # the pre-snapshot behavior byte-for-byte). In-memory nodes
        # have no home to keep snapshot files in — plane disabled.
        from tendermint_tpu.utils import knobs as _knobs
        self._snap_interval = _knobs.knob_int(
            "TM_TPU_SNAPSHOT_INTERVAL",
            config=getattr(config.base, "snapshot_interval", 0))
        self._snap_keep = _knobs.knob_int(
            "TM_TPU_SNAPSHOT_KEEP",
            config=getattr(config.base, "snapshot_keep", 2), default=2)
        self._snap_chunk_kb = _knobs.knob_int(
            "TM_TPU_SNAPSHOT_CHUNK_KB",
            config=getattr(config.base, "snapshot_chunk_kb", 256),
            default=256)
        self._retain_heights = _knobs.knob_int(
            "TM_TPU_RETAIN_HEIGHTS",
            config=getattr(config.base, "retain_heights", 0))
        self._state_sync = _knobs.knob_bool(
            "TM_TPU_STATE_SYNC",
            config=getattr(config.base, "state_sync", False))
        self.snapshot_store = None
        self._statesync_dir = ""
        if not in_memory:
            from tendermint_tpu.storage import SnapshotStore
            data_dir = config.path(config.base.db_dir)
            self.snapshot_store = SnapshotStore(
                os.path.join(data_dir, "snapshots"))
            self._statesync_dir = os.path.join(data_dir, "statesync")
        else:
            self._snap_interval = 0
            self._retain_heights = 0
            self._state_sync = False

        if client_creator is None:
            if app is None:
                from tendermint_tpu.abci.apps import KVStoreApp
                app = KVStoreApp()
            client_creator = local_client_creator(app)
        self.app = app
        self.app_conns = AppConns(client_creator)

        # verification plane: the process-wide verifier unless the config
        # asks for a non-default backend/mesh (config knob per VERDICT r2
        # — a node on a multi-device host shards over every chip via
        # mesh="auto"; mesh kernels are cached per size so several
        # in-process nodes share one compiled kernel). Built before the
        # FIRST verification path (handshake replay) so every path in the
        # node — replay, block exec, evidence — uses the SAME configured
        # verifier.
        from tendermint_tpu.models.verifier import (BatchVerifier,
                                                    default_verifier)
        vb = getattr(config.base, "verifier_backend", "auto")
        vm = str(getattr(config.base, "verifier_mesh", "auto"))
        if (vb, vm) == ("auto", "auto"):
            # all-default: in-process testnets and the shard plane
            # share the process-wide verifier, its mesh and its stats
            self.verifier = default_verifier()
        else:
            self.verifier = BatchVerifier(vb, mesh=vm)

        # a state-sync restore a crash tore mid-apply is repaired HERE,
        # before the handshake reads the stores (the apply is
        # idempotent; incomplete downloads are left for the reactor)
        if self._statesync_dir and os.path.isdir(self._statesync_dir) \
                and self.app is not None:
            from tendermint_tpu.statesync import resume_pending_restore
            resume_pending_restore(
                self._statesync_dir, self.block_store, self.state_store,
                self.snapshot_store, self.app, gen_doc.chain_id,
                verifier=self.verifier, logger=self.logger)

        # ABCI handshake: sync app with stores (consensus/replay.go:211)
        handshaker = Handshaker(self.state_store, self.block_store, gen_doc,
                                verifier=self.verifier,
                                snapshot_store=self.snapshot_store,
                                app=self.app)
        state = handshaker.handshake(self.app_conns)

        if mempool is None:
            from tendermint_tpu.mempool import Mempool
            mempool = Mempool(
                self.app_conns.mempool, config=config.mempool,
                height=state.last_block_height,
                wal_dir=(None if in_memory or
                         not getattr(config.mempool, "wal_dir", "")
                         else config.path(config.mempool.wal_dir)))
        self.mempool = mempool

        if evidence_pool is None:
            from tendermint_tpu.evidence import EvidencePool, EvidenceStore
            evidence_pool = EvidencePool(
                EvidenceStore(open_db(db_path("evidence"))), state,
                state_store=self.state_store, verifier=self.verifier)
        self.evidence_pool = evidence_pool

        self.event_bus = EventBus()
        self.block_exec = BlockExecutor(
            self.state_store, self.app_conns.consensus,
            mempool=mempool, evidence_pool=evidence_pool,
            event_bus=self.event_bus, verifier=self.verifier)

        if in_memory:
            from tendermint_tpu.storage.wal import NilWAL
            self.wal = NilWAL()
        else:
            self.wal = WAL(config.path(config.consensus.wal_path),
                           light=config.consensus.wal_light,
                           readonly=wal_readonly)

        self.consensus = ConsensusState(
            config.consensus, state, self.block_exec, self.block_store,
            mempool=mempool, evidence_pool=evidence_pool,
            priv_validator=priv_validator, wal=self.wal,
            event_bus=self.event_bus, ticker_factory=TimeoutTicker)
        if hasattr(mempool, "txs_available_hook"):
            mempool.txs_available_hook = lambda: self.consensus.submit(
                {"type": "txs_available"})

        # recovery plane: interval snapshots + retention + pruning on
        # the commit path (and, below, on the fast-sync apply path)
        self.snapshots = None
        if self.snapshot_store is not None and \
                (self._snap_interval > 0 or self._retain_heights > 0):
            from tendermint_tpu.storage import SnapshotManager
            self.snapshots = SnapshotManager(
                self.snapshot_store, self.state_store, self.block_store,
                self.app, interval=self._snap_interval,
                keep=self._snap_keep,
                chunk_size=self._snap_chunk_kb * 1024,
                retain_heights=self._retain_heights)
            self.consensus.post_commit_hooks.append(
                self.snapshots.maybe_snapshot)

        # ------------------------------------------------ p2p reactor stack
        self.switch = None
        self.fast_sync = fast_sync
        # `node_key=` is the p2p identity where the caller holds it (an
        # in-memory node whose peers dial it by id: serving/worker.py)
        self._given_node_key = node_key
        if with_p2p:
            self._build_p2p(state, fast_sync, in_memory)

        self.rpc_server = None
        self.rpc_address = None
        self.grpc_server = None
        self.with_rpc = with_rpc

        # tx indexer + service (node/node.go:294-320)
        from tendermint_tpu.state.txindex import (
            IndexerService, KVTxIndexer, NullTxIndexer)
        if config.tx_index.indexer == "kv":
            tags = [t for t in config.tx_index.index_tags.split(",") if t]
            self.tx_indexer = KVTxIndexer(
                open_db(db_path("tx_index")), index_tags=tags,
                index_all_tags=config.tx_index.index_all_tags)
        else:
            self.tx_indexer = NullTxIndexer()
        self.indexer_service = IndexerService(self.tx_indexer,
                                              self.event_bus)

    def _ensure_loop(self):
        """The node's ONE event loop (async reactor core) when the
        TM_TPU_REACTOR mode resolves to 'loop'; None in thread mode.
        Shared by the p2p switch AND the RPC listener — one selector
        owns every socket of the node."""
        from tendermint_tpu.p2p.conn import loop as _loop_cfg
        if self.loop is None and _loop_cfg.resolve() == "loop":
            self.loop = _loop_cfg.ReactorLoop(
                name=f"tm-reactor-loop-{os.getpid()}")
        return self.loop

    def _build_p2p(self, state, fast_sync: bool, in_memory: bool) -> None:
        """node/node.go:235-265: switch + reactors (+PEX)."""
        from tendermint_tpu.blockchain import BlockchainReactor
        from tendermint_tpu.consensus.reactor import ConsensusReactor
        from tendermint_tpu.evidence import EvidenceReactor
        from tendermint_tpu.mempool import MempoolReactor
        from tendermint_tpu.p2p import NodeInfo, NodeKey, Switch

        if self._given_node_key is not None:
            node_key = self._given_node_key
        elif in_memory:
            from tendermint_tpu.types.keys import PrivKey
            node_key = NodeKey(PrivKey.generate())
        else:
            node_key = NodeKey.load_or_generate(
                self.config.path("config/node_key.json"))
        self.node_key = node_key
        # compact-plane capabilities ride the handshake's `other` list;
        # empty (hence byte-identical handshake) with the knobs off
        from tendermint_tpu.consensus import compact as _compact
        node_info = NodeInfo(
            pubkey=node_key.pubkey,
            moniker=getattr(self.config.base, "moniker", "node"),
            network=self.gen_doc.chain_id,
            other=_compact.wire_capabilities())
        if self.config.p2p.region_delay_ms:
            # link delay by region: peers hold what they send here by
            # the region this node names
            node_info.other.append(f"region={self.config.p2p.region}")
        self.switch = Switch(self.config.p2p, node_key, node_info,
                             loop=self._ensure_loop())

        # the p2p identity IS the node label everywhere observability
        # correlates: the causal trace plane (wire stamps + dumps), the
        # keepalive-RTT provider the merger cross-checks against, and
        # the process-global log context (grep-by-node across a
        # testnet's interleaved logs)
        from tendermint_tpu.telemetry import causal as _causal
        from tendermint_tpu.utils import log as _log
        _causal.set_node(node_info.id[:12])
        _causal.set_rtt_provider(
            lambda: {p.id[:12]: p.rtt_s
                     for p in self.switch.peers.list()})
        _log.bind(node=node_info.id[:8])

        self.consensus_reactor = ConsensusReactor(
            self.consensus, fast_sync=fast_sync,
            gossip_sleep_s=self.config.consensus.peer_gossip_sleep_ms / 1e3)
        # state sync only engages on a node with NOTHING below it: a
        # genesis-fresh store joining an established chain
        restore = bool(self._state_sync and fast_sync and
                       self.snapshot_store is not None and
                       self.app is not None and
                       state.last_block_height == 0)
        self._statesync_gate = None
        if restore:
            import threading as _threading
            self._statesync_gate = _threading.Event()
        expect_peers = bool(self.config.p2p.persistent_peers or
                            self.config.p2p.seeds)
        self.blockchain_reactor = BlockchainReactor(
            state, self.block_exec, self.block_store, fast_sync=fast_sync,
            consensus_reactor=self.consensus_reactor,
            gate=self._statesync_gate, expect_peers=expect_peers,
            redial=self._dial_configured_peers,
            after_apply=(self.snapshots.maybe_snapshot
                         if self.snapshots is not None else None))
        if self.snapshots is not None:
            reactor = self.blockchain_reactor
            self.snapshots.peer_floor = \
                lambda: reactor.min_peer_height() + 1
        self.mempool_reactor = MempoolReactor(
            self.mempool, broadcast=self.config.mempool.broadcast)
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)

        self.statesync_reactor = None
        if self.snapshot_store is not None and \
                (restore or self._snap_interval > 0):
            # the channel is only advertised when the recovery plane is
            # on — peers without it never see 0x60 traffic (try_send
            # checks the remote's advertised channels)
            from tendermint_tpu.statesync import StateSyncReactor
            self.statesync_reactor = StateSyncReactor(
                self.snapshot_store, self.gen_doc.chain_id,
                restore=restore, statesync_dir=self._statesync_dir,
                block_store=self.block_store,
                state_store=self.state_store, app=self.app,
                verifier=self.verifier,
                on_restored=self._on_state_sync_done)

        self.switch.add_reactor("mempool", self.mempool_reactor)
        self.switch.add_reactor("blockchain", self.blockchain_reactor)
        self.switch.add_reactor("consensus", self.consensus_reactor)
        self.switch.add_reactor("evidence", self.evidence_reactor)
        if self.statesync_reactor is not None:
            self.switch.add_reactor("statesync", self.statesync_reactor)

        from tendermint_tpu.p2p.trust import TrustMetricStore
        from tendermint_tpu.storage import open_db as _open
        self.trust_store = TrustMetricStore(
            _open(None if in_memory else
                  self.config.path(self.config.base.db_dir, "trust.db")))
        self.switch.trust_store = self.trust_store

        if self.config.p2p.pex:
            from tendermint_tpu.p2p.pex import AddrBook, PEXReactor
            book_path = None if in_memory else \
                self.config.path("config/addrbook.json")
            self.addr_book = AddrBook(
                path=book_path, strict=self.config.p2p.addr_book_strict)
            self.pex_reactor = PEXReactor(
                self.addr_book, seed_mode=self.config.p2p.seed_mode)
            self.switch.add_reactor("pex", self.pex_reactor)
            self.switch.addr_book = self.addr_book

    def start(self, dial: bool = True) -> None:
        """`dial` false leaves the configured peers undialled: a
        launcher of many nodes on one host (serving/deploy.py) has
        every node listen first and dial (`dial_configured_peers`)
        only then, so that no outgoing connection can take a port that
        a node has yet to bind, and no dial comes too early."""
        self.logger.info("starting node",
                         chain_id=self.gen_doc.chain_id,
                         height=self.consensus.state.last_block_height,
                         fast_sync=self.fast_sync)
        # WAL catchup for the in-flight height (consensus/replay.go:93).
        # In fast-sync mode the consensus reactor replays at
        # switch_to_consensus instead — replaying now would be wiped by
        # the post-sync state reset.
        if not self.fast_sync:
            try:
                catchup_replay(self.consensus, self.wal)
            except ValueError as e:
                # missing marker for a committed height / multi-height
                # WAL over genesis state: not fatal (the node proceeds
                # without replay, same as before) but must be visible
                self.logger.error("WAL catchup replay skipped", err=str(e))

        if self.loop is not None:
            self.loop.start()

        if self.switch is not None:
            host, port = _parse_laddr(self.config.p2p.laddr)
            self.switch.listen(host, port)
            if hasattr(self, "addr_book"):
                self.addr_book.add_our_address(self.switch.listen_address)
            self.switch.start()  # starts all reactors; consensus reactor
            #                      starts the state machine unless fast-sync
            if dial:
                self._dial_configured_peers()
        else:
            self.consensus.start()

        self.indexer_service.start()

        # stall-detector flight recorder (TM_TPU_TRACE on + a nonzero
        # TM_TPU_TRACE_STALL_S window): no height progress for the
        # window dumps the causal timeline + consensus state for
        # post-mortem, once per stall episode
        from tendermint_tpu.telemetry import causal as _causal
        from tendermint_tpu.utils import knobs as _knobs
        stall_s = _knobs.knob_float("TM_TPU_TRACE_STALL_S", default=0.0)
        if _causal.enabled() and stall_s > 0:
            self._stall_detector = _causal.StallDetector(
                lambda: self.height, self._on_stall, stall_s)
            self._stall_detector.start()

        # runtime introspection: start the sampler when TM_TPU_PROF
        # says so, and the queue-observatory watcher whenever the
        # observatory is on (both process-global daemons — in-process
        # testnets share them; node.stop() leaves them for peers)
        from tendermint_tpu.telemetry import profile as _profile
        from tendermint_tpu.telemetry import queues as _queues
        _profile.maybe_start()
        _queues.ensure_watch()

        # HTTP and gRPC listeners are independent: asking for one must
        # not bind the other (a gRPC-only operator should not get the
        # full JSON-RPC surface on the config-default 0.0.0.0 address)
        if self.with_rpc or self.config.rpc.grpc_laddr:
            from tendermint_tpu.rpc import RPCEnv, make_server
            # loop mode: the RPC/WebSocket listener runs on the SAME
            # event loop as the p2p plane (rpc/aserver.py) — no thread
            # per connection; thread mode keeps the ThreadingHTTPServer
            rpc_loop = self._ensure_loop() if self.with_rpc else None
            if rpc_loop is not None and not rpc_loop.running:
                rpc_loop.start()
            self.rpc_server, core = make_server(RPCEnv.from_node(self),
                                                loop=rpc_loop)
            if self.with_rpc:
                host, port = _parse_laddr(self.config.rpc.laddr)
                self.rpc_address = self.rpc_server.serve(host, port)
            if self.config.rpc.grpc_laddr:
                from tendermint_tpu.rpc.grpc_service import BroadcastAPIServer
                self.grpc_server = BroadcastAPIServer(
                    core, self.config.rpc.grpc_laddr)
                self.grpc_server.start()
                self.logger.info("grpc broadcast api listening",
                                 port=self.grpc_server.port)

    def _on_state_sync_done(self, state) -> None:
        """State-sync restore concluded. On success every store is
        bootstrapped at the snapshot height — adopt the state across
        the node's live components; either way, release the fast-sync
        gate so block sync proceeds (from the snapshot, or from
        genesis on fallback)."""
        if state is not None:
            self.consensus.state = state
            self.blockchain_reactor.adopt_restored(state)
            self.evidence_pool.state = state
            self.mempool.update(state.last_block_height, [])
            self.logger.info("state sync complete; fast-syncing tail",
                             height=state.last_block_height)
        if self._statesync_gate is not None:
            self._statesync_gate.set()

    def dial_configured_peers(self) -> None:
        """What `start(dial=False)` left out."""
        self._dial_configured_peers()

    def _dial_configured_peers(self) -> None:
        from tendermint_tpu.p2p import NetAddress
        persistent = [a for a in
                      self.config.p2p.persistent_peers.split(",") if a]
        seeds = [a for a in self.config.p2p.seeds.split(",") if a]
        if persistent:
            self.switch.dial_peers_async(
                [NetAddress.from_string(a) for a in persistent],
                persistent=True)
        if seeds:
            self.switch.dial_peers_async(
                [NetAddress.from_string(a) for a in seeds])

    def _on_stall(self, height: int, stalled_s: float) -> None:
        """Flight-recorder dump: the causal timeline plus the same
        consensus snapshot the dump_consensus_state RPC serves, written
        where a post-mortem will look (the node's data dir when it has
        one, else the system tempdir)."""
        import json
        import tempfile
        import time as _time
        from tendermint_tpu.rpc import RPCCore, RPCEnv
        from tendermint_tpu.telemetry import causal as _causal
        from tendermint_tpu.telemetry import profile as _profile
        from tendermint_tpu.telemetry import queues as _queues
        doc = {"height": height, "stalled_s": round(stalled_s, 3),
               "timeline": _causal.dump(),
               # self-diagnosing capture: WHERE the threads are (the
               # profiler's table, whatever it has collected) and WHICH
               # queue backed up first (the observatory's high-water
               # table) ride along with the what-happened timeline
               "profile": _profile.snapshot(),
               "queues": _queues.table()}
        try:
            core = RPCCore(RPCEnv.from_node(self))
            doc["consensus"] = core.dump_consensus_state()
        except Exception as e:
            doc["consensus_error"] = repr(e)
        out_dir = tempfile.gettempdir()
        if self.config.home:
            d = self.config.path(self.config.base.db_dir)
            if os.path.isdir(d):
                out_dir = d
        path = os.path.join(
            out_dir, f"tm_stall_h{height}_{int(_time.time())}.json")
        try:
            with open(path, "w") as f:
                json.dump(doc, f)
            self.logger.error("consensus stalled: flight recorder dumped",
                              height=height,
                              stalled_s=round(stalled_s, 1), path=path)
        except OSError as e:
            self.logger.error("stall dump failed", err=repr(e))

    def stop(self) -> None:
        if getattr(self, "_stall_detector", None) is not None:
            self._stall_detector.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        self.indexer_service.stop()
        if self.switch is not None:
            self.switch.stop()
            if getattr(self, "trust_store", None) is not None:
                self.trust_store.save()
        else:
            self.consensus.stop()
        if self.loop is not None and self._owns_loop:
            # after the switch: peer teardowns run ON the loop. A
            # shared (injected) loop belongs to its creator — the
            # shard set stops it once, after every node is down.
            self.loop.stop()
        if hasattr(self.mempool, "close"):
            self.mempool.close()
        self.app_conns.close()
        if hasattr(self.wal, "close"):
            self.wal.close()

    @property
    def height(self) -> int:
        return self.consensus.state.last_block_height


def default_node(home: str, app=None, in_memory=False,
                 with_p2p=False, fast_sync=None) -> Node:
    """DefaultNewNode (node/node.go:79): load config tree from `home`."""
    from tendermint_tpu.config import default_config
    config = default_config(home)
    gen_doc = GenesisDoc.load(os.path.join(home, "config", "genesis.json"))
    pv = PrivValidatorFile.load_or_generate(
        os.path.join(home, "config", "priv_validator.json"))
    if fast_sync is None:
        fast_sync = with_p2p and getattr(config.base, "fast_sync", True)
    return Node(config, gen_doc, priv_validator=pv, app=app,
                in_memory=in_memory, with_p2p=with_p2p,
                fast_sync=fast_sync)
