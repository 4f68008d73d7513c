"""Shard plane — N independent chains in one process, one shared
verifier, one front door.

Millions of users do not fit through one totally-ordered log; the
production answer is horizontal sharding. This package runs N
INDEPENDENT chains (distinct genesis docs, valsets and on-disk homes)
inside one process:

- ``set.py``     — ShardSet: assembles N ``Node`` values sharing the
                   process-default verifier and its mesh and ONE
                   ReactorLoop; node assembly is a value, not an
                   ambient (the forcing function that purged the
                   remaining process-global state from node.py).
- ``router.py``  — ShardRouter: deterministic key-space -> chain
                   mapping (hash-range over the tx key prefix) wired
                   into the async RPC front door; one listener serves
                   ``broadcast_tx_*``, ``abci_query`` and WebSocket
                   subscriptions for every shard, with ``tm_shard_*``
                   telemetry.
- ``reads.py``   — certified cross-shard reads: a query against shard
                   B answered to a client of shard A ships the value
                   plus a ``ContinuousCertifier``-backed commit proof,
                   so cross-shard reads are certified, not trusted.

The shards share one verifier, so one key table, one mesh and one set
of compiled kernels serve every chain's bulk verifies; a live vote is
verified on the host by the thread that received it, whatever the
shard count.

Knob: ``TM_TPU_SHARDS`` (> ``config.base.shards`` > 0) sets the default
shard count a ``ShardSet(n_shards=None)`` assembles; 0 keeps the
single-chain deployment shape untouched.
"""

from __future__ import annotations

from tendermint_tpu.utils import knobs as _knobs


def resolve_shards(config: int = 0) -> int:
    """Default shard count: env TM_TPU_SHARDS > config.base.shards >
    0 (sharding off)."""
    return max(0, _knobs.knob_int("TM_TPU_SHARDS", config=config))


from tendermint_tpu.shard.reads import (  # noqa: E402,F401
    CertifiedReader,
    ReadProofError,
    full_commit_at,
)
from tendermint_tpu.shard.router import (  # noqa: E402,F401
    ShardMap,
    ShardRouter,
    key_prefix,
    make_shard_server,
)
from tendermint_tpu.shard.set import ShardSet  # noqa: E402,F401
