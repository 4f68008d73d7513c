"""KVStore app — the reference's "dummy" app, upgraded with a Merkle state.

Txs are "key=value" (or opaque bytes stored under themselves). The app
hash is a Merkle root (ops/merkle) over N_BUCKETS bucket digests; a
bucket digest commits to an additive accumulator (sum of its keys'
pair digests mod 2^256, plus the key count), so a key change is O(1)
and a commit is O(changed keys + dirty buckets) — state-size
independent, where a naive rebuild is O(total state) per block and
comes to dominate long syncs. The reference's dummy gets
incrementality from its IAVL tree; the hash value itself is
app-defined in both builds. (Additive set-hashing trades collision
margin for O(1) updates — the known generalized-birthday attacks need
~2^80+ work per bucket, acceptable for this demo app.)

The commit backend is the chain's choice: the genesis says
`app_state["kvstore"]["commit_backend"]`, "tree" or "buckets", and
InitChain honours it before the first DeliverTx. "tree" is the
authenticated state tree (tendermint_tpu/statetree/, docs/state.md):
app_hash becomes a critbit Merkle root, `query(prove=True)` returns
per-key inclusion/absence proofs bound to it, and snapshot chunks
stream straight from tree nodes. The two backends produce DIFFERENT
app hashes by design, which is why the genesis states it. A genesis
that says nothing leaves the choice to TM_TPU_STATE_TREE=on in each
node's environment, as before; one that speaks wins over it.

A chain may also start with state: `app_state["kvstore"]["records"]`
names a file of records (abci/apps/records.py: `file`, `count`,
`sha256`). InitChain checks the digest, loads every record through the
same loader a snapshot restore uses, and commits the store as version
0, the one block 1 builds on. A file that is not the one the genesis
names stops the node.

Validator-change txs (the reference's persistent_dummy surface):
`val:<pubkey_hex>/<power>` queues a validator update returned from
EndBlock — power 0 removes the validator. This is how the reactor
valset-change scenarios drive membership churn through consensus.

The app tracks the active validator set (seeded from InitChain,
maintained from applied updates) and REJECTS invalid updates at
DeliverTx time — removal of an unknown validator, or a batch that would
empty the set — mirroring persistent_dummy's updateValidator guard. The
core treats an invalid EndBlock update as a consensus failure and
halts, so the app must be the gate that keeps bad updates from ever
reaching it: without this, one unauthenticated broadcast_tx naming an
unknown pubkey with power 0 would halt the whole network.
"""

from __future__ import annotations

import hashlib
import zlib

from tendermint_tpu.abci.app import BaseApplication
from tendermint_tpu.abci.types import (
    ResultCheckTx, ResultDeliverTx, ResultEndBlock, ResultInfo,
    ResultQuery, UniformDeliverResults, ValidatorUpdate,
)
from tendermint_tpu.ops import merkle
from tendermint_tpu.telemetry import trace

BACKENDS = ("tree", "buckets")

N_BUCKETS = 256   # app-hash buckets; must be a power of two. Tradeoff:
#                   bucket re-hash cost grows with state/N_BUCKETS, the
#                   per-commit root costs N_BUCKETS-1 node hashes — 256
#                   balances both for ~10^4-10^6 keys
# digest of an empty bucket (leaf hash of no pairs)
_EMPTY_BUCKET = hashlib.sha256(b"\x00").digest()


class _NativeStoreView:
    """Read-only Mapping facade over the native KV core, so callers
    (query, info, tests doing `app.store.get`/`dict(app.store)`) see
    the same dict-like surface the pure-Python app exposes."""

    def __init__(self, mod, core):
        self._mod = mod
        self._core = core

    def get(self, k, default=None):
        v = self._mod.get(self._core, k)
        return default if v is None else v

    def __getitem__(self, k):
        v = self._mod.get(self._core, k)
        if v is None:
            raise KeyError(k)
        return v

    def __contains__(self, k):
        return self._mod.get(self._core, k) is not None

    def __len__(self):
        return self._mod.size(self._core)

    def __bool__(self):
        return len(self) > 0

    def items(self):
        return self._mod.items(self._core)

    def keys(self):
        return [k for k, _ in self.items()]

    def __iter__(self):
        return iter(self.keys())


class _TreeStoreView:
    """Mapping facade over a StateTree so every caller of `app.store`
    (deliver_tx writes, query/info reads, tests doing dict(app.store))
    sees the same dict-like surface the other two cores expose. Reads
    hit the WORKING tree (pre-commit state, same semantics as the dict
    path); versioned/proven reads go through the tree directly."""

    def __init__(self, tree):
        self._tree = tree

    def get(self, k, default=None):
        v = self._tree.get(k)
        return default if v is None else v

    def __getitem__(self, k):
        v = self._tree.get(k)
        if v is None:
            raise KeyError(k)
        return v

    def __setitem__(self, k, v):
        self._tree.set(k, v)

    def __delitem__(self, k):
        if not self._tree.delete(k):
            raise KeyError(k)

    def __contains__(self, k):
        return self._tree.get(k) is not None

    def __len__(self):
        return len(self._tree)

    def __bool__(self):
        return len(self._tree) > 0

    def items(self):
        # live iteration: walk the working root under the tree lock
        with self._tree._lock:
            stack = [self._tree._root] if self._tree._root is not None \
                else []
            out = []
            while stack:
                node = stack.pop()
                if hasattr(node, "key"):
                    out.append((node.key, node.value))
                else:
                    stack.append(node.right)
                    stack.append(node.left)
            return out

    def keys(self):
        return [k for k, _ in self.items()]

    def __iter__(self):
        return iter(self.keys())


class KVStoreApp(BaseApplication):
    def __init__(self, use_native: bool = True):
        # commit backend selection (ISSUE 16): the authenticated state
        # tree gives per-key proofs bound to app_hash, at the cost of
        # O(log n) hashing per touched key; the bucketed accumulator
        # (below) gives none. The two produce DIFFERENT app hashes by
        # design (pinned by test). Until InitChain has read the genesis
        # (init_chain), the node's environment chooses.
        from tendermint_tpu.utils import knobs
        self._use_native = use_native
        self._reset_store(knobs.knob_bool("TM_TPU_STATE_TREE"))
        self.height = 0
        self.app_hash = b""
        self.tx_count = 0
        self._val_updates: list[ValidatorUpdate] = []
        # pubkey -> power of the ACTIVE set, as the app knows it: seeded
        # by init_chain, advanced immediately by its own accepted updates
        # (persistent_dummy mutates app state at DeliverTx time too, so
        # several val txs in one block see each other's effects)
        self._validators: dict[bytes, int] = {}
        self._val_seeded = False

    def _reset_store(self, tree: bool) -> None:
        """An empty store on the backend asked for."""
        self._tree = None
        if tree:
            from tendermint_tpu.statetree import StateTree
            self._tree = StateTree()
        # native core (kvcore.cpp): the plain-kv DeliverTx path, the
        # bucketed accumulator, and the commit hash in C++ — the pure
        # Python fields below stay authoritative when it is absent
        # (TM_TPU_NO_NATIVE / no compiler / use_native=False), and the
        # two implementations are differential-tested for identical
        # app hashes. The tree IS the store: no C++ kv core beside it
        from tendermint_tpu import native
        self._kvmod = native.kv() if self._use_native and not tree else None
        if self._kvmod is not None:
            self._core = self._kvmod.kv_new()
            self.store = _NativeStoreView(self._kvmod, self._core)
        elif self._tree is not None:
            self._core = None
            self.store = _TreeStoreView(self._tree)
        else:
            self._core = None
            self.store: dict[bytes, bytes] = {}
        # incremental app-hash state (see commit()): keys spread over
        # fixed buckets; each bucket holds an ADDITIVE accumulator (sum
        # of pair digests mod 2^256) so a key change is O(1) regardless
        # of state size
        self._bucket_acc: list[int] = [0] * N_BUCKETS
        self._bucket_count: list[int] = [0] * N_BUCKETS
        # flat digest buffer (bucket b at [32b:32b+32]) — handed to the
        # native merkle kernel without join/copy
        self._bucket_digest = bytearray(_EMPTY_BUCKET * N_BUCKETS)
        self._pair_digest: dict[bytes, bytes] = {}
        self._dirty: set[bytes] = set()

    def init_chain(self, validators, chain_id: str = "",
                   app_state=None) -> None:
        self._validators = {v.pubkey: v.power for v in validators}
        self._val_seeded = True
        mine = (app_state or {}).get("kvstore") or {}
        backend = mine.get("commit_backend")
        if backend is not None:
            if backend not in BACKENDS:
                raise ValueError(f"genesis app_state.kvstore.commit_backend "
                                 f"{backend!r} is none of {BACKENDS}")
            if (backend == "tree") != (self._tree is not None):
                # the chain's choice, not this node's
                from tendermint_tpu.utils.log import get_logger
                get_logger("kvstore", chain=chain_id).info(
                    "the genesis chooses the commit backend; this node's "
                    "environment said otherwise", genesis=backend,
                    environment="tree" if self._tree is not None
                    else "buckets")
                self._reset_store(backend == "tree")
        if mine.get("records") is not None:
            from tendermint_tpu.abci.apps.records import read_records
            # version 0: what block 1 builds on, so that block 1's
            # commit rehashes block 1's records and not the store
            self._load_items(read_records(mine["records"]), 0)

    def info(self) -> ResultInfo:
        return ResultInfo(data=f"kvstore:{len(self.store)}",
                          version="1",
                          last_block_height=self.height,
                          last_block_app_hash=self.app_hash)

    def check_tx(self, tx: bytes) -> ResultCheckTx:
        if not tx:
            return ResultCheckTx(code=1, log="empty tx")
        return ResultCheckTx()

    def deliver_tx(self, tx: bytes) -> ResultDeliverTx:
        if not tx:
            return ResultDeliverTx(code=1, log="empty tx")
        if tx.startswith(b"val:"):
            try:
                pk_hex, _, power = tx[4:].partition(b"/")
                update = ValidatorUpdate(bytes.fromhex(pk_hex.decode()),
                                         int(power))
                if len(update.pubkey) != 32 or update.power < 0:
                    raise ValueError(tx)
            except (ValueError, UnicodeDecodeError):
                return ResultDeliverTx(code=1, log=f"bad val tx {tx!r}")
            # fault injection (reference fail-point spirit, utils/fail.py):
            # tests set TM_KVSTORE_UNSAFE_VAL_UPDATES to bypass the guard
            # and drive the core's ApplyBlockError/halt path end-to-end
            import os as _os
            # tmlint: allow(taint): test-only fault hook in utils/fail.py spirit; never set outside tests that deliberately break the guard
            guard = not _os.environ.get("TM_KVSTORE_UNSAFE_VAL_UPDATES")
            if update.power == 0:
                if guard and update.pubkey not in self._validators:
                    return ResultDeliverTx(
                        code=2, log="cannot remove unknown validator "
                        f"{pk_hex.decode()[:16]}")
                # the "would empty the set" check needs the full picture;
                # an unseeded app (no InitChain) can't distinguish "last
                # validator" from "last one I happen to know about"
                if guard and self._val_seeded and \
                        len(self._validators) == 1:
                    return ResultDeliverTx(
                        code=3, log="validator set would be empty")
                self._validators.pop(update.pubkey, None)
            else:
                self._validators[update.pubkey] = update.power
            self._val_updates.append(update)
            self.tx_count += 1
            return ResultDeliverTx(tags={"val": pk_hex.decode()[:16]})
        if b"=" in tx:
            k, _, v = tx.partition(b"=")
        else:
            k = v = tx
        if self._core is not None:
            self._kvmod.set_one(self._core, k, v)
        else:
            self.store[k] = v
            if self._tree is None:
                self._dirty.add(k)
        self.tx_count += 1
        return ResultDeliverTx(tags={"app.key": k.decode("utf-8", "replace")})

    def deliver_tx_batch(self, txs):
        """One native call for a block of plain kv txs; any empty or
        `val:` tx routes the whole batch through the per-tx path (the
        native core scans before mutating, so no partial application).
        Returns a lazy UniformDeliverResults — same per-tx results on
        access, none of the 5,000-object construction up front."""
        if self._core is not None and txs:
            out = self._kvmod.deliver_batch(self._core, txs)
            if isinstance(out, tuple):
                n, packed = out
                self.tx_count += len(txs)
                return UniformDeliverResults(None, packed=packed, n=n)
        return [self.deliver_tx(tx) for tx in txs]

    def commit(self) -> bytes:
        # App hash = Merkle root over N_BUCKETS bucket digests; a bucket
        # digest commits to its additive accumulator (sum of pair
        # digests mod 2^256) + key count. O(changed keys) per commit,
        # state-size independent — see the module docstring for the
        # construction and its tradeoff.
        self.height += 1
        if self._tree is not None:
            # authenticated path: rehash the dirty subtree, register
            # version `height` (the app_hash a header at height+1
            # carries — provers serve reads against retained versions)
            self.app_hash = self._tree.commit(self.height)
            return self.app_hash
        if self._core is not None:
            self.app_hash = self._kvmod.commit(self._core)
            return self.app_hash
        if self._dirty:
            sha = hashlib.sha256
            pd = self._pair_digest
            acc, cnt = self._bucket_acc, self._bucket_count
            dirty_buckets = set()
            for k in self._dirty:
                b = zlib.crc32(k) & (N_BUCKETS - 1)
                dirty_buckets.add(b)
                old = pd.get(k)
                v = self.store.get(k)
                if v is None:
                    if old is not None:
                        del pd[k]
                        acc[b] -= int.from_bytes(old, "little")
                        cnt[b] -= 1
                else:
                    # pair digest: sha(len k|k|len v|v) — cached per key
                    d = sha(len(k).to_bytes(4, "little") + k
                            + len(v).to_bytes(4, "little") + v).digest()
                    if old is not None:
                        acc[b] -= int.from_bytes(old, "little")
                    else:
                        cnt[b] += 1
                    acc[b] += int.from_bytes(d, "little")
                    pd[k] = d
            self._dirty.clear()
            for b in dirty_buckets:
                if cnt[b] == 0:
                    d = _EMPTY_BUCKET
                else:
                    d = sha(b"\x00"
                            + (acc[b] % (1 << 256)).to_bytes(32, "little")
                            + cnt[b].to_bytes(8, "little")).digest()
                self._bucket_digest[32 * b:32 * b + 32] = d
        if not self.store:
            self.app_hash = b"\x00" * 32
        else:
            self.app_hash = merkle.root_from_digests_host(
                self._bucket_digest)
        return self.app_hash

    def end_block(self, height: int) -> ResultEndBlock:
        updates, self._val_updates = self._val_updates, []
        return ResultEndBlock(validator_updates=updates)

    # -- state-sync snapshot surface ------------------------------------------

    def snapshot_items(self):
        """The complete kv state in a deterministic order, so two
        nodes at the same height publish byte-identical snapshot
        payloads. Bucket cores sort by key (a materialized copy); the
        tree backend STREAMS straight from the committed version's
        nodes in key-hash order — copy-on-write keeps the iterator a
        consistent snapshot even while later blocks commit, so
        GB-scale state never gets a second in-memory copy."""
        if self._tree is not None:
            return self._tree.items_at(self.height)
        return sorted(self.store.items())

    def restore_items(self, items, height: int, validators=None) -> bytes:
        """Install a snapshot's kv state wholesale through the one
        loader (`_load_items`); the height bookkeeping lands on exactly
        `height`. The resulting hash MUST match the snapshot state's
        app_hash — the caller verifies and aborts on mismatch. A
        snapshot taken by a BUCKET-mode chain recomputes to a different
        app_hash on the tree and the caller's verify aborts: restoring
        across commit backends is a config error, not a silent adopt."""
        if validators is not None:
            self._validators = {bytes(pk): int(power)
                                for pk, power in validators}
            self._val_seeded = True
        self._val_updates = []
        return self._load_items(items, height)

    def _load_items(self, items, height: int) -> bytes:
        """The one bulk path into the store, for a snapshot restore and
        for the genesis' records alike: an empty store on the backend in
        use, every pair loaded (the tree's one-pass build; the bucket
        cores' ordinary set path), and the app hash computed by the
        ordinary commit() machinery as version `height`."""
        self._reset_store(self._tree is not None)
        if self._tree is not None:
            self._tree.load(items)
        elif self._core is not None:
            for k, v in items:
                self._kvmod.set_one(self._core, bytes(k), bytes(v))
        else:
            for k, v in items:
                self.store[bytes(k)] = bytes(v)
                self._dirty.add(bytes(k))
        self.height = height - 1
        return self.commit()  # height -> `height`, app_hash recomputed

    def query(self, path: str, data: bytes, height: int,
              prove: bool) -> ResultQuery:
        if self._tree is not None and (prove or height):
            # versioned (and optionally proven) read against a
            # COMMITTED tree version. height 0 = the latest commit.
            # The proof binds (key, value-or-absence) to that
            # version's app_hash — the hash the header at height+1
            # carries, which a lite client can certify.
            version = int(height) if height else self.height
            with trace.span("app.query", req=version, prove=int(prove)):
                return self._query_version(data, version, prove)
        with trace.span("app.query", req=self.height, prove=0):
            value = self.store.get(data, b"")
            return ResultQuery(key=data, value=value, height=self.height,
                               log="exists" if value else "does not exist")

    def _query_version(self, data: bytes, version: int,
                       prove: bool) -> ResultQuery:
        try:
            if prove:
                value, pf = self._tree.prove(data, version)
            else:
                value, pf = self._tree.get(data, version), None
        except KeyError as e:
            return ResultQuery(code=1, key=data, height=version,
                               log=str(e))
        proof_bytes = b""
        if pf is not None:
            from tendermint_tpu.statetree import proof_to_bytes
            proof_bytes = proof_to_bytes(pf)
        # a present key whose value is empty and an absent key both
        # carry b"" here: `log` and the proof's `present` tell them apart
        return ResultQuery(
            key=data, value=value or b"", proof=proof_bytes,
            height=version,
            log="exists" if value is not None else "does not exist")
