"""Deployment topologies (ISSUE 19).

A ``Topology`` is the declarative shape of a multi-process net; a
``materialize`` call turns it into real per-node homes under one
output directory — shared genesis, per-node priv_validator/node_key,
config.json with persistent_peers wired — plus the argv each process
runs with. Two kinds:

- ``validators``: N validator processes (the ``cli testnet`` file
  tree, full persistent-peer mesh) plus M edge replicas. A replica
  home carries the SAME genesis and its own node_key but NO
  priv_validator.json — the trust-model floor (docs/serving.md): an
  edge process must never be able to sign.
- ``shardset``: one process assembling a ShardSet (N in-process
  chains behind one front door) — the sharded front-door shape the
  load harness sweeps.

Ports follow one convention: process k gets
(base+2k, base+2k+1) as (p2p, rpc) so harnesses can derive every
address from the base alone.

A net of many validators on one host (a 100-validator deployment on 13
cores) is the same kind with more fields set, each off by default:
`powers` (unequal stake in the genesis), `dial_k` (each validator dials
K peers of a seeded draw in place of the full mesh; the draw is kept
only if the graph is connected), `n_workers` (several validators to a
worker process, `cli worker`: serving/worker.py), `in_process` (the
validators the calling process hosts itself, because a chip belongs to
one process: Deployment.local_nodes), `regions` with `region_delay_ms`
(validator i lives in region i mod regions; p2p/fuzz.py's delay mode
holds every frame sent to another region), and `key_seed` (keys and
draws from a seed, so that a run can be made again).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

#: consensus timeouts for 1-core CI hosts (the e2e-test profile —
#: tests/test_e2e_testnet.py uses these numbers)
FAST_TIMEOUTS = {
    "timeout_propose": 400, "timeout_propose_delta": 100,
    "timeout_prevote": 200, "timeout_prevote_delta": 100,
    "timeout_precommit": 200, "timeout_precommit_delta": 100,
    "timeout_commit": 100,
}


@dataclass
class Topology:
    kind: str = "validators"        # validators | shardset
    n_validators: int = 3
    n_replicas: int = 0
    n_shards: int = 2               # shardset kind only
    chain_id: str = "serving-net"
    base_port: int = 0              # 0 = caller allocates (utils/procs)
    wire: Optional[dict] = None     # WireProxy fault spec between vals
    wire_seed: int = 0
    fast_timeouts: bool = True
    max_seconds: float = 900.0      # child self-destruct deadline
    env: Dict[str, str] = field(default_factory=dict)  # extra child env
    # ---- many validators on one host (module docstring); the defaults
    # leave the shapes above as they were
    powers: Optional[Sequence[int]] = None   # stake per validator (10 each)
    key_seed: Optional[int] = None  # keys, genesis time and draws from it
    timeouts: Optional[dict] = None  # consensus timeouts, written out;
    #                                  wins over fast_timeouts
    dial_k: int = 0                 # 0 = full persistent mesh
    addr_book_strict: bool = False  # upstream's default is True: a strict
    #                                 book keeps no loopback address, so
    #                                 on one host pex then dials nothing
    n_workers: int = 0              # 0 = one process a validator
    in_process: Sequence[int] = ()  # validators the caller hosts itself
    rpc_validators: Optional[Sequence[int]] = None   # None = all serve RPC
    in_memory: bool = False         # stores of worker and in-process nodes
    regions: int = 0                # validator i lives in region i % regions
    region_delay_ms: Optional[Sequence[Sequence[float]]] = None  # one-way
    region_jitter_ms: float = 0.0
    verifier_backend: str = "auto"  # config.base.verifier_backend
    telemetry: bool = True          # config.base.telemetry
    log_level: Optional[str] = None  # config.base.log_level where set

    def n_processes(self) -> int:
        if self.kind == "shardset":
            return 1
        if self.n_workers > 0:
            return self.n_workers
        return self.n_validators + self.n_replicas

    def hosted_by_worker(self) -> List[List[int]]:
        """Worker w's validators: those the caller does not host,
        dealt round by index."""
        mine = set(self.in_process)
        rest = [k for k in range(self.n_validators) if k not in mine]
        return [rest[w::self.n_workers] for w in range(self.n_workers)]

    def region_of(self, k: int) -> int:
        return k % self.regions if self.regions > 0 else 0


@dataclass
class ProcSpec:
    """One spawnable process of a materialized topology."""
    name: str                        # val0.. / replica0.. / shardset
    kind: str                        # validator | replica | shardset
    home: str
    argv: List[str]
    p2p_port: int                    # 0 for shardset and worker
    rpc_port: int
    nodes: Sequence[str] = ()        # a worker's validators, by name

    @property
    def rpc_address(self) -> str:
        return f"http://127.0.0.1:{self.rpc_port}"


def draw_peer_graph(n: int, k: int, seed) -> List[List[int]]:
    """dials[i]: the k validators that validator i dials, drawn without
    replacement from the other n - 1 by a generator seeded from `seed`
    and the attempt's number; the first draw whose undirected graph is
    connected is kept. The same arguments give the same draw."""
    if not 0 < k < n:
        raise ValueError(f"cannot dial {k} of {n - 1} other validators")
    for attempt in range(64):
        rng = random.Random(f"{seed}/peer-graph/{attempt}")
        dials = [sorted(rng.sample([j for j in range(n) if j != i], k))
                 for i in range(n)]
        links = graph_links(dials)
        near: Dict[int, set] = {i: set() for i in range(n)}
        for a, b in links:
            near[a].add(b)
            near[b].add(a)
        seen, edge = {0}, [0]
        while edge:
            for j in near[edge.pop()] - seen:
                seen.add(j)
                edge.append(j)
        if len(seen) == n:
            return dials
    raise RuntimeError(f"no connected draw of {k} dials among {n} "
                       f"validators in 64 attempts (seed {seed!r})")


def graph_links(dials: Sequence[Sequence[int]]) -> List[tuple]:
    """The undirected links of a dial list: (a, b) with a < b, once
    where both dial each other."""
    return sorted({(min(i, j), max(i, j))
                   for i, row in enumerate(dials) for j in row})


def _write_configs(out: str, topo: Topology, base: int,
                   node_keys, n_total: int) -> None:
    from tendermint_tpu.config import default_config, save_config
    dials = (draw_peer_graph(topo.n_validators, topo.dial_k,
                             seed_of(topo))
             if topo.dial_k > 0 else None)
    for k in range(n_total):
        is_val = k < topo.n_validators
        name = f"val{k}" if is_val else f"replica{k - topo.n_validators}"
        home = os.path.join(out, name)
        cfg = default_config(home)
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base + 2 * k}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base + 2 * k + 1}"
        cfg.p2p.addr_book_strict = topo.addr_book_strict
        if is_val and dials is not None:
            # a sparse graph: this validator's own K dials
            peers = [f"{node_keys[j].id()}@127.0.0.1:{base + 2 * j}"
                     for j in dials[k]]
        elif is_val:
            # full validator mesh (the testnet shape)
            peers = [f"{node_keys[j].id()}@127.0.0.1:{base + 2 * j}"
                     for j in range(topo.n_validators) if j != k]
        else:
            # replicas dial ONLY validators: edge processes follow the
            # chain, they are not gossip hubs for each other
            peers = [f"{node_keys[j].id()}@127.0.0.1:{base + 2 * j}"
                     for j in range(topo.n_validators)]
        cfg.p2p.persistent_peers = ",".join(peers)
        cfg.base.verifier_backend = topo.verifier_backend
        cfg.base.telemetry = topo.telemetry
        if topo.log_level is not None:
            cfg.base.log_level = topo.log_level
        if is_val and topo.regions > 0 and topo.region_delay_ms:
            cfg.p2p.region = topo.region_of(k)
            cfg.p2p.region_delay_ms = [
                float(ms) for ms in topo.region_delay_ms[cfg.p2p.region]]
            cfg.p2p.region_jitter_ms = float(topo.region_jitter_ms)
            cfg.p2p.region_delay_seed = int(seed_of(topo))
        # the load harness searches txs by tag (app.key); index them
        cfg.tx_index.index_all_tags = True
        save_config(cfg)
        if topo.timeouts is not None:
            _patch_consensus(home, topo.timeouts)
        elif topo.fast_timeouts:
            _patch_consensus(home, FAST_TIMEOUTS)


def seed_of(topo: Topology) -> int:
    """What a topology's draws are seeded from."""
    return topo.wire_seed if topo.key_seed is None else topo.key_seed


def _patch_consensus(home: str, timeouts: dict) -> None:
    path = os.path.join(home, "config", "config.json")
    cfg = json.load(open(path))
    cfg.setdefault("consensus", {}).update(timeouts)
    json.dump(cfg, open(path, "w"))


def materialize(topo: Topology, out: str) -> List[ProcSpec]:
    """Write the file tree for `topo` under `out` and return the
    process specs to spawn. `topo.base_port` must be set (a free
    block of 2 * n_processes ports — utils/procs.free_port_block)."""
    base = topo.base_port
    if base <= 0:
        raise ValueError("materialize needs topo.base_port set")
    os.makedirs(out, exist_ok=True)

    if topo.kind == "shardset":
        home = os.path.join(out, "shardset")
        os.makedirs(home, exist_ok=True)
        argv = [sys.executable, "-m", "tendermint_tpu.cli",
                "--home", home, "shardset",
                "--shards", str(topo.n_shards),
                "--laddr", f"tcp://127.0.0.1:{base + 1}",
                "--max-seconds", str(topo.max_seconds)]
        return [ProcSpec("shardset", "shardset", home, argv,
                         p2p_port=0, rpc_port=base + 1)]

    if topo.kind != "validators":
        raise ValueError(f"unknown topology kind {topo.kind!r}")

    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.types import GenesisDoc, PrivValidatorFile
    from tendermint_tpu.types.genesis import GenesisValidator

    n_total = topo.n_validators + topo.n_replicas
    if topo.powers is not None and len(topo.powers) != topo.n_validators:
        raise ValueError(f"{len(topo.powers)} powers for "
                         f"{topo.n_validators} validators")
    if topo.n_workers > 0 and topo.n_replicas:
        raise ValueError("worker processes host validators only")
    keys = None if topo.key_seed is None else \
        random.Random(f"{topo.key_seed}/keys")
    pvs, node_keys = [], []
    for k in range(n_total):
        is_val = k < topo.n_validators
        name = f"val{k}" if is_val else f"replica{k - topo.n_validators}"
        cfg_dir = os.path.join(out, name, "config")
        os.makedirs(cfg_dir, exist_ok=True)
        pv_path = os.path.join(cfg_dir, "priv_validator.json")
        nk_path = os.path.join(cfg_dir, "node_key.json")
        if keys is not None:
            # keys from the seed: written where load_or_generate below
            # finds them
            from tendermint_tpu.types import PrivKey
            if is_val and not os.path.exists(pv_path):
                PrivValidatorFile.generate(pv_path, keys.randbytes(32))
            if not os.path.exists(nk_path):
                NodeKey(PrivKey.generate(keys.randbytes(32))).save(nk_path)
        if is_val:
            # ONLY validators get a signing key on disk
            pvs.append(PrivValidatorFile.load_or_generate(pv_path))
        node_keys.append(NodeKey.load_or_generate(nk_path))
    powers = topo.powers or [10] * topo.n_validators
    gen = GenesisDoc(
        chain_id=topo.chain_id,
        genesis_time_ns=time.time_ns() if topo.key_seed is None else 1,
        validators=[GenesisValidator(pv.pubkey.ed25519, int(power))
                    for pv, power in zip(pvs, powers)])
    for k in range(n_total):
        is_val = k < topo.n_validators
        name = f"val{k}" if is_val else f"replica{k - topo.n_validators}"
        gen.save(os.path.join(out, name, "config", "genesis.json"))
    _write_configs(out, topo, base, node_keys, n_total)

    specs: List[ProcSpec] = []
    if topo.n_workers > 0:
        # several validators to a process; those of `in_process` are
        # the caller's to build (Deployment.local_nodes)
        for w, hosted in enumerate(topo.hosted_by_worker()):
            names = [f"val{k}" for k in hosted]
            argv = [sys.executable, "-m", "tendermint_tpu.cli",
                    "--home", out, "worker", "--nodes", ",".join(names),
                    "--max-seconds", str(topo.max_seconds)]
            if topo.in_memory:
                argv.append("--in-memory")
            rpc = [f"val{k}" for k in hosted
                   if topo.rpc_validators is None or
                   k in topo.rpc_validators]
            if rpc:
                argv += ["--rpc", ",".join(rpc)]
            specs.append(ProcSpec(f"worker{w}", "worker", out, argv,
                                  p2p_port=0, rpc_port=0, nodes=names))
        return specs
    for k in range(n_total):
        is_val = k < topo.n_validators
        name = f"val{k}" if is_val else f"replica{k - topo.n_validators}"
        home = os.path.join(out, name)
        rpc = base + 2 * k + 1
        if is_val:
            argv = [sys.executable, "-m", "tendermint_tpu.cli",
                    "--home", home, "node", "--p2p", "--no-fast-sync",
                    "--rpc-laddr", f"tcp://127.0.0.1:{rpc}",
                    "--max-seconds", str(topo.max_seconds)]
        else:
            argv = [sys.executable, "-m", "tendermint_tpu.cli",
                    "--home", home, "replica",
                    "--rpc-laddr", f"tcp://127.0.0.1:{rpc}",
                    "--max-seconds", str(topo.max_seconds)]
        specs.append(ProcSpec(
            name, "validator" if is_val else "replica", home, argv,
            p2p_port=base + 2 * k, rpc_port=rpc))
    return specs
