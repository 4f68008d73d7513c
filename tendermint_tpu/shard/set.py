"""ShardSet — N independent chains assembled as VALUES in one process
(ISSUE 15).

Each shard is a full ``Node`` (its own genesis doc, valset, stores,
WAL, mempool, consensus state machine) with a DISTINCT chain id and —
when a home directory is given — its own on-disk home. What the shards
SHARE is the process-wide verification plane: the default verifier
(one key table, one set of compiled kernels and one mesh for every
chain's bulk verifies), and one ``ReactorLoop`` for the whole
process's sockets (the front door listener plus any node-level loop
use).

Assembly is value-scoped, not ambient: every node's logger carries a
``chain=<id>`` field, per-shard telemetry rides a bounded ``chain``
label (``tm_shard_height``), the shared verifier holds nothing a
stopping node would have to release, and the shared loop is stopped
once by the set, never by a member node. The ``ambient-singleton``
tmlint checker (analysis/checkers/ambient.py) keeps it that way: new
module-level mutable singletons outside the blessed catalog fail the
build."""

from __future__ import annotations

import hashlib
import os
from typing import Callable, Dict, List, Optional

from tendermint_tpu.shard import resolve_shards
from tendermint_tpu.shard.router import _m_height


class ShardSet:
    """Assemble, run and tear down N single-process chains.

    ``n_shards=None`` resolves the TM_TPU_SHARDS knob. ``home=None``
    runs every shard in memory (the bench/test shape); a directory
    gives each shard its own ``<home>/<chain_id>`` on-disk home.
    ``config_factory(i, chain_id)`` / ``app_factory(i, chain_id)``
    customize per-shard config and ABCI app (defaults: test-profile
    consensus timeouts + KVStoreApp)."""

    def __init__(self, n_shards: Optional[int] = None,
                 chain_prefix: str = "shard", home: Optional[str] = None,
                 config_factory: Optional[Callable] = None,
                 app_factory: Optional[Callable] = None):
        from tendermint_tpu.config import test_config
        from tendermint_tpu.node import Node
        from tendermint_tpu.types import (
            GenesisDoc,
            GenesisValidator,
            PrivKey,
        )
        from tendermint_tpu.types.priv_validator import (
            LocalSigner,
            PrivValidator,
        )

        n = n_shards if n_shards is not None else resolve_shards()
        if n < 1:
            raise ValueError(f"ShardSet needs >= 1 shard, got {n}")
        self.home = home
        self.loop = None
        self.rpc_server = None
        self.rpc_address = None
        self.router = None
        self.nodes: List = []
        self._started = False
        for i in range(n):
            chain_id = f"{chain_prefix}-{i:02d}"
            # deterministic per-chain validator key: the shard curve's
            # arms and their single-chain controls sign identically
            key = PrivKey.generate(
                hashlib.sha256(chain_id.encode()).digest())
            gen = GenesisDoc(
                chain_id=chain_id, genesis_time_ns=1,
                validators=[GenesisValidator(key.pubkey.ed25519, 10)])
            if config_factory is not None:
                cfg = config_factory(i, chain_id)
            else:
                cfg = test_config(
                    os.path.join(home, chain_id) if home else "")
            app = app_factory(i, chain_id) if app_factory else None
            node = Node(cfg, gen,
                        priv_validator=PrivValidator(LocalSigner(key)),
                        app=app, in_memory=home is None,
                        with_p2p=False, loop=self.ensure_loop())
            # per-shard telemetry scoping: height per chain, updated on
            # the commit path (bounded label — the chain ids are ours)
            gauge = _m_height.labels(chain_id)
            gauge.set(node.consensus.state.last_block_height)
            node.consensus.post_commit_hooks.append(
                lambda state, g=gauge: g.set(state.last_block_height))
            self.nodes.append(node)
        self.chains: List[str] = [nd.gen_doc.chain_id
                                  for nd in self.nodes]
        self._by_chain: Dict[str, int] = {
            c: i for i, c in enumerate(self.chains)}

    # ------------------------------------------------------- assembly

    def ensure_loop(self):
        """The ONE shared ReactorLoop of the shard plane (front door +
        every member node). Created lazily, started with the set."""
        if self.loop is None:
            from tendermint_tpu.p2p.conn.loop import ReactorLoop
            self.loop = ReactorLoop(name="tm-shard-loop")
        return self.loop

    def __len__(self) -> int:
        return len(self.nodes)

    def __iter__(self):
        return iter(self.nodes)

    def node_for_chain(self, chain_id: str):
        i = self._by_chain.get(chain_id)
        if i is None:
            raise KeyError(f"unknown chain {chain_id!r}")
        return self.nodes[i]

    def node_for_key(self, key: bytes):
        return self.nodes[self.router_map().shard_of(bytes(key))]

    def router_map(self):
        from tendermint_tpu.shard.router import ShardMap
        if self.router is not None:
            return self.router.map
        return ShardMap(self.chains)

    # ------------------------------------------------------ lifecycle

    def start(self) -> None:
        for node in self.nodes:
            node.start()
        self._started = True

    def serve(self, host: str = "127.0.0.1", port: int = 0) -> tuple:
        """Open the one front door: an AsyncRPCServer on the shared
        loop serving the router's merged route table. Returns the
        bound (host, port)."""
        from tendermint_tpu.shard.router import make_shard_server
        if self.rpc_server is not None:
            return self.rpc_address
        self.rpc_server, self.router = make_shard_server(
            self, loop=self.ensure_loop())
        self.rpc_address = self.rpc_server.serve(host, port)
        return self.rpc_address

    def reader(self, verifier=None):
        """An in-process certified cross-shard reader (shard/reads.py)
        over this set — what a shard-A-resident client uses to read
        shard B without trusting it."""
        from tendermint_tpu.shard.reads import CertifiedReader
        if self.router is None:
            from tendermint_tpu.shard.router import ShardRouter
            self.router = ShardRouter(self)
        return CertifiedReader(shard_set=self, verifier=verifier)

    def heights(self) -> Dict[str, int]:
        return {nd.gen_doc.chain_id:
                nd.consensus.state.last_block_height
                for nd in self.nodes}

    def frontier(self) -> int:
        """The minimum committed height across shards (the laggard)."""
        return min(self.heights().values())

    def stop(self) -> None:
        """Tear the set down. Order-independent per node (verifier
        ownership is construction-recorded); the shared loop stops
        LAST, after every node released its sockets/timers."""
        if self.rpc_server is not None:
            self.rpc_server.stop()
            self.rpc_server = None
        for node in self.nodes:
            try:
                node.stop()
            except Exception as e:
                # one shard's teardown failure must not leak the rest
                node.logger.error("shard node stop failed", err=repr(e))
        if self.loop is not None:
            self.loop.stop()
            self.loop = None
        self._started = False
