"""Configuration tree (config/config.go:35-44).

One Config value with per-subsystem sections; consensus timeouts are
round-scaled functions exactly like the reference's (config/config.go:
364-385: propose 3000+500·round ms, prevote/precommit 1000+500·round ms,
commit 1000 ms). test_config() shrinks everything for fast in-process
nets, mirroring config.TestConfig.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace


@dataclass
class BaseConfig:
    chain_id: str = ""
    moniker: str = "anonymous"
    fast_sync: bool = True
    db_dir: str = "data"
    log_level: str = "info"
    prof_laddr: str = ""
    # signature-verification plane (no reference equivalent — the
    # reference verifies scalar on one core, types/validator_set.go:257):
    # backend auto|jax|python; mesh auto|off|N shards verify batches over
    # the device mesh (models/verifier.py). The env knob TM_TPU_MESH
    # additionally routes big ops/merkle roots (tx root, part-set root)
    # through the same mesh — see docs/knobs.md.
    verifier_backend: str = "auto"
    verifier_mesh: str = "auto"
    # telemetry plane (telemetry/): metrics + tracing on by default; the
    # namespace prefixes every exposed metric (tm_verifier_batch_size).
    # Env TM_TPU_TELEMETRY=off overrides `telemetry` unconditionally.
    telemetry: bool = True
    telemetry_namespace: str = "tm"
    # p2p burst frame plane (p2p/conn/burst.py): seal/open whole frame
    # bursts in one native AEAD call and coalesce up to p2p_burst_max
    # packets per link write. auto|on|off; TM_TPU_P2P_BURST (off|on|
    # auto|<max packets>) wins over these. `off` restores the per-frame
    # send/recv routines byte-for-byte.
    p2p_burst: str = "auto"
    p2p_burst_max: int = 0  # 0 = burst.DEFAULT_MAX_PACKETS (64)
    # pipelined block hot path (pipeline.py): native part-set build,
    # streaming proposal gossip, overlapped finalize and group-commit
    # persistence. auto|on|off; TM_TPU_PIPELINE wins over this. "off"
    # restores the serial per-height code byte-for-byte.
    pipeline: str = "auto"
    # compact consensus gossip (consensus/compact.py): `compact` relays
    # proposals as header + salted short tx ids (receivers rebuild the
    # block from their mempool, fetch only missing txs, and fall back
    # to full part gossip on miss/timeout); `vote_agg` batches the
    # votes a peer lacks into one message verified as one coalesced
    # dispatch. auto|on|off each; TM_TPU_COMPACT / TM_TPU_VOTE_AGG win.
    # Both off = today's wire bytes byte-for-byte.
    compact: str = "auto"
    vote_agg: str = "auto"
    # causal tracing plane (telemetry/causal.py): per-height consensus
    # spans, trace-stamped p2p envelopes, the dump_height_timeline RPC
    # and the stall-detector flight recorder. off (the default) keeps
    # the wire format byte-for-byte untraced. TM_TPU_TRACE wins.
    trace: str = "off"
    # chaos plane (chaos/): deterministic fault injection. "off" (the
    # default) is a zero-overhead no-op — p2p links stay on the
    # existing code paths byte-for-byte. Any other value is a link
    # fault spec, e.g. "drop=0.05,delay=0.1,delay_ms=30"; chaos_seed
    # makes the injected fault pattern reproducible. Env TM_TPU_CHAOS
    # (which may carry its own seed=N) wins over both.
    chaos: str = "off"
    chaos_seed: int = 0
    # recovery plane (storage/snapshot.py + statesync/): chunked state
    # snapshots every `snapshot_interval` heights (0 = off), newest
    # `snapshot_keep` retained; `retain_heights` > 0 prunes block/state
    # stores behind the combined floor (never below the latest
    # snapshot, the evidence horizon, or a peer's catch-up frontier);
    # `state_sync` lets a fresh node join by fetching a snapshot over
    # p2p instead of replaying every block. TM_TPU_SNAPSHOT_INTERVAL /
    # _KEEP / _CHUNK_KB, TM_TPU_RETAIN_HEIGHTS and TM_TPU_STATE_SYNC
    # win over these; everything 0/off = today's behavior byte-for-byte.
    snapshot_interval: int = 0
    snapshot_keep: int = 2
    snapshot_chunk_kb: int = 256
    retain_heights: int = 0
    state_sync: bool = False
    # runtime introspection plane (telemetry/profile.py + queues.py):
    # `prof` on starts the sampling profiler at `prof_hz` sweeps/sec
    # (tm_prof_* metrics, GET /debug/pprof, the debug_profile RPC);
    # `queue_watch` (off | on | <poll seconds>) runs the bounded-queue
    # catalog + saturation watchdog behind /healthz. TM_TPU_PROF /
    # _PROF_HZ / _QUEUE_WATCH win over these.
    prof: str = "off"
    prof_hz: float = 0.0  # 0 = profile.DEFAULT_HZ (13)
    queue_watch: str = "on"
    # tx-lifecycle SLO plane (telemetry/slo.py): `slo` on stamps
    # sampled txs at each stage boundary (front-door admit -> CheckTx
    # -> proposal -> commit -> publish -> WS delivery) into per-stage
    # quantile sketches served at /slo and folded into /healthz;
    # `slo_sample` is the deterministic hash-based sampling rate.
    # TM_TPU_SLO / TM_TPU_SLO_SAMPLE win over these.
    slo: str = "off"
    slo_sample: float = 1.0
    # async reactor core (p2p/conn/loop.py): "loop" (= auto, the
    # default) runs every peer socket, gossip routine and RPC/WebSocket
    # connection on ONE selector event loop per node; "threads"
    # restores the thread-per-connection plane byte-for-byte (the
    # wire-parity / chaos-replay escape hatch). TM_TPU_REACTOR wins.
    reactor: str = "auto"
    # shard plane (shard/): default chain count a ShardSet(n_shards=
    # None) assembles — N independent chains in one process behind one
    # front door, sharing the process-default verifier and one
    # ReactorLoop. 0 keeps the single-chain deployment shape.
    # TM_TPU_SHARDS wins.
    shards: int = 0


@dataclass
class RPCConfig:
    laddr: str = "tcp://0.0.0.0:46657"
    grpc_laddr: str = ""
    unsafe: bool = False


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:46656"
    seeds: str = ""
    persistent_peers: str = ""
    max_num_peers: int = 50
    flush_throttle_ms: int = 100
    max_msg_packet_payload_size: int = 1024
    send_rate: int = 512000  # B/s (p2p/conn/connection.go:33-35)
    recv_rate: int = 512000
    pex: bool = True
    seed_mode: bool = False
    addr_book_strict: bool = True
    skip_upnp: bool = True   # opt-in UPnP (reference default differs;
    #                          zero-egress/test environments must not probe)
    handshake_timeout_s: float = 20.0   # TOTAL handshake deadline
    dial_timeout_s: float = 3.0
    # hostile-peer hardening (ISSUE 13; env TM_TPU_P2P_BAN_SCORE /
    # _BAN_BASE_S / _FD_HEADROOM win): trust score below ban_score =>
    # banned for ban_base_s (doubling per repeat, decaying with clean
    # time); inbound accepts shed when fewer than fd_headroom fds
    # remain under the process limit
    ban_score: int = 30
    ban_base_s: float = 60.0
    fd_headroom: int = 64
    # link delay by region (p2p/fuzz.py's delay mode, the reference's
    # own seam; serving/topology.py writes these): the region this node
    # advertises in NodeInfo.other, and per region of the peer the
    # one-way delay in ms this node holds every frame it sends there,
    # plus up to region_jitter_ms drawn per frame from a generator
    # seeded per link from region_delay_seed. A peer that names no
    # region, or one the list gives no delay above 0, keeps its link
    # untouched. Empty (the default) = off: no link is wrapped.
    region: int = 0
    region_delay_ms: list = field(default_factory=list)
    region_jitter_ms: float = 0.0
    region_delay_seed: int = 0


@dataclass
class MempoolConfig:
    recheck: bool = True
    broadcast: bool = True
    wal_dir: str = "data/mempool.wal"
    size: int = 100000
    cache_size: int = 100000


@dataclass
class ConsensusConfig:
    wal_path: str = "data/cs.wal/wal"
    wal_light: bool = False
    # base timeouts in ms (config/config.go defaults)
    timeout_propose: int = 3000
    timeout_propose_delta: int = 500
    timeout_prevote: int = 1000
    timeout_prevote_delta: int = 500
    timeout_precommit: int = 1000
    timeout_precommit_delta: int = 500
    timeout_commit: int = 1000
    skip_timeout_commit: bool = False
    max_block_size_txs: int = 10000
    create_empty_blocks: bool = True
    create_empty_blocks_interval: int = 0  # seconds
    peer_gossip_sleep_ms: int = 100
    peer_query_maj23_sleep_ms: int = 2000

    def propose_timeout_s(self, round_: int) -> float:
        return (self.timeout_propose
                + self.timeout_propose_delta * round_) / 1000.0

    def prevote_timeout_s(self, round_: int) -> float:
        return (self.timeout_prevote
                + self.timeout_prevote_delta * round_) / 1000.0

    def precommit_timeout_s(self, round_: int) -> float:
        return (self.timeout_precommit
                + self.timeout_precommit_delta * round_) / 1000.0

    def commit_timeout_s(self) -> float:
        return self.timeout_commit / 1000.0


@dataclass
class TxIndexConfig:
    indexer: str = "kv"           # kv | null
    index_tags: str = ""
    index_all_tags: bool = False


@dataclass
class Config:
    home: str = ""
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)

    def path(self, *parts: str) -> str:
        return os.path.join(self.home, *parts)


def default_config(home: str = "") -> Config:
    """Defaults, overlaid with `<home>/config/config.json` when present
    (the reference loads $TMHOME/config.toml via viper, config/toml.go)."""
    cfg = Config(home=home)
    path = os.path.join(home, "config", "config.json") if home else ""
    if path and os.path.exists(path):
        import json
        with open(path) as f:
            overrides = json.load(f)
        for section, values in overrides.items():
            target = getattr(cfg, section, None)
            if target is None or not isinstance(values, dict):
                continue
            for k, v in values.items():
                if hasattr(target, k):
                    setattr(target, k, v)
    return cfg


def save_config(cfg: Config) -> str:
    """Persist the non-default sections as config/config.json."""
    import json
    from dataclasses import asdict
    path = os.path.join(cfg.home, "config", "config.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    obj = {name: asdict(getattr(cfg, name))
           for name in ("base", "rpc", "p2p", "mempool", "consensus",
                        "tx_index")}
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
    return path


def test_config(home: str = "") -> Config:
    """All consensus timeouts shrunk ~30x (config.TestConfig)."""
    c = Config(home=home)
    c.consensus = replace(
        c.consensus,
        timeout_propose=100, timeout_propose_delta=1,
        timeout_prevote=10, timeout_prevote_delta=1,
        timeout_precommit=10, timeout_precommit_delta=1,
        timeout_commit=10, skip_timeout_commit=True,
        peer_gossip_sleep_ms=5, peer_query_maj23_sleep_ms=250)
    return c
