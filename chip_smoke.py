#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process, holding the chip from start to finish, drives the main
paths once through the entry points a user calls, at upstream's own
shapes (BASELINE.json `configs`; only chain length is cut, listed under
each leg's `reduced`), on data made in-process from --seed:

  A  one 8,192-lane chunk through default_verifier() whose lanes carry
     the encodings RFC 8032 leaves open or OpenSSL is lenient on (s + L,
     an off-curve key, y >= p, the small-order keys), lane by lane
     against the scalar host oracle. The 10,000-validator commit itself
     is the cell commit_10kv.verify_commit; these lanes are what its
     `correct` does not send through the kernel.
  B  config 4: fast-sync of 64-validator x 5,000-tx blocks through
     BlockchainReactor at verify_window=256, app-hash chain and store
     compared with the builder's serial apply; a forged precommit stops
     a second sync exactly below the forgery and punishes the peer.
  C  config 5: a 4,096-header x 64-validator lite chain signed ON THE
     DEVICE (ops/ed25519.sign_batch, sample compared with OpenSSL) and
     certified by lite.certify_chain; a forged header is rejected at
     its height.
  D  config 1: four complete Nodes on loopback TCP in this process (a
     chip belongs to one process), upstream's default timeouts, driven
     through JSONRPCClient; every write is read back from another node.
     Runs last, in a process that has imported JAX and used the device,
     which is what ops/merkle.py routes on.

Legs B and C take their chains from the benchmark's builders
(benchmark/chain.py) and leg B its reactor and instant peer from
benchmark/drivers/sync.py: what starts here is what the cells measure.

It refuses to start unless every device JAX reports is a TPU, fails if
a native extension did not build from the sources in the tree, asserts
on the chip that legs A-C ran on compiled Pallas kernels only, and
prints which kernel, backend and Merkle/SHA implementation served each
leg, as counts. No exception is caught and reported as a field: a leg
that fails ends the run with a non-zero exit code.

EVERY TIME PRINTED HERE IS SMOKE OUTPUT: one cold or warm pass on a
shared host, compiles included where it says so. None is a benchmark
number and none belongs in README.md or docs/.

The last line of standard output is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import shutil
import struct
import sys
import tempfile
import time

# upstream's shapes (BASELINE.json configs 4, 5, 1); lengths cut
B_VALIDATORS, B_TXS, B_TX_BYTES, B_WINDOW = 64, 5_000, 250, 256
B_BLOCKS = 2 * B_WINDOW + 32      # two full windows + a bucket-2048 tail
B_FORGED_BLOCKS, B_FORGED_AT = 32, 21
C_HEADERS, C_VALIDATORS = 4_096, 64
C_FORGED_HEADERS, C_FORGED_AT = 32, 20
D_NODES, D_RPC_NODES, D_WRITES = 4, 2, 20

# ops/ed25519.predecomp_stats() keys that count device dispatches
KERNELS = ("pallas_full", "pallas_pre", "jnp_full", "jnp_pre", "mesh_jnp",
           "decompress", "sign_pallas", "sign_scalar")
# the jitted function behind each kernel name, as jax.monitoring sees it
KERNEL_FN = {"pallas_full": "_verify_from_bytes_pallas",
             "pallas_pre": "_verify_pre_pallas",
             "jnp_full": "_verify_from_bytes_jnp",
             "jnp_pre": "_verify_pre_jnp",
             "mesh_jnp": "_verify_from_bytes_jnp",
             "decompress": "_decompress_to_bytes",
             "sign_pallas": "_sign_rb_pallas"}


def say(kind: str, **fields) -> None:
    print(json.dumps({"smoke": kind, **fields}, sort_keys=True), flush=True)


class CompileLog:
    """What JAX itself reports about compiling: per jitted function the
    trace, lowering and backend-compile seconds of each compile in
    order, and persistent-cache hits and misses."""

    _EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
               "/jax/core/compile/backend_compile_duration":
                   "backend_compile_s"}

    def __init__(self):
        import jax
        self.secs: dict = {}    # (fun_name, field) -> [seconds, ...]
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        field = self._EVENTS.get(event)
        if field is not None:
            name = str(kw.get("fun_name", "?"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.secs.setdefault((name, field), []).append(round(secs, 3))

    def _event(self, event, **kw):
        key = event.rsplit("/", 1)[-1]
        if key in self.cache:
            self.cache[key] += 1

    def table(self, first_call_s: dict) -> dict:
        """Per "kernel[shape]": the seconds its first dispatch spent in
        the jit call (the package's own record), joined in dispatch
        order with JAX's split of it. The join holds when JAX reported
        exactly one compile per shape; otherwise the raw lists are
        given beside the shapes."""
        by_kernel: dict = {}
        for key, secs in first_call_s.items():
            by_kernel.setdefault(key.split("[")[0], []).append((key, secs))
        out = {}
        for kernel, shapes in by_kernel.items():
            fn = KERNEL_FN.get(kernel)
            split = {f: self.secs.get((fn, f), [])
                     for f in self._EVENTS.values()}
            joined = all(len(v) == len(shapes) for v in split.values())
            for i, (key, secs) in enumerate(shapes):
                out[key] = {"first_call_s": secs}
                if joined:
                    out[key].update({f: v[i] for f, v in split.items()})
            if not joined:
                out[f"{kernel}:unjoined"] = split
        return out


def kernel_counts() -> dict:
    from tendermint_tpu.ops import ed25519
    s = ed25519.predecomp_stats()
    return {k: s[k] for k in KERNELS + ("full", "fill", "hit")}


def merkle_counts() -> dict:
    from tendermint_tpu import telemetry
    out = {}
    for fam, impls in (("merkle_roots_total", ("native", "host", "mesh")),
                       ("merkle_sha_batches_total",
                        ("native", "host", "device"))):
        for impl in impls:
            out[f"tm_{fam}{{impl={impl}}}"] = int(
                telemetry.value(fam, {"impl": impl}) or 0)
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def require(cond, what: str) -> None:
    # not `assert`: the checks must survive python -O
    if not cond:
        raise AssertionError(what)


def require_pallas_only(leg: str, kernels: dict) -> None:
    """Legs A-C on the chip: every device dispatch took a compiled
    Pallas kernel (ops/ed25519 never asks for interpret mode), none the
    jnp ladder, none scalar signing."""
    off = {k: kernels[k] for k in
           ("jnp_full", "jnp_pre", "mesh_jnp", "sign_scalar") if kernels[k]}
    require(not off, f"leg {leg}: dispatches off the Pallas kernels: {off}")


def spec_merkle_root(items) -> bytes:
    """ops/merkle.py's tree spec written out with hashlib alone."""
    sha = hashlib.sha256
    level = [sha(b"\x00" + it).digest() for it in items]
    m = 1
    while m < len(level):
        m *= 2
    level += [b"\x00" * 32] * (m - len(level))
    while len(level) > 1:
        level = [sha(b"\x01" + level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return sha(b"\x02" + struct.pack("<Q", len(items)) + level[0]).digest()


# ---------------------------------------------------------------- leg A

def lenient_lanes(items, rng) -> dict:
    """lane -> (case, item): the replacements no cell's `correct` sends
    through the kernel. The y >= p and x = 0 encodings are OpenSSL's
    leniency gap (types/keys._noncanonical_point), which the scalar
    path routes to the RFC 8032 reference so that scalar and batch
    verdicts cannot split; the signatures crafted for them satisfy the
    verification equation for the identity point."""
    from tendermint_tpu.ops.ed25519 import L_ORDER
    from tendermint_tpu.utils import ed25519_ref as ref

    p255 = (1 << 255) - 19

    def small_order_sig():
        # R = s*B: satisfies s*B - h*A == R for A = the identity point,
        # whatever the message
        s = rng.randrange(1, L_ORDER)
        return ref.point_compress(ref.point_mul(s, ref.BASE)) + \
            s.to_bytes(32, "little")

    def off_curve_pubkey():
        y = 2
        while ref.point_decompress(y.to_bytes(32, "little")) is not None:
            y += 1
        return y.to_bytes(32, "little")

    def with_sign(enc: bytes) -> bytes:
        return enc[:31] + bytes([enc[31] | 0x80])

    identity = (1).to_bytes(32, "little")
    cases = [
        ("s_plus_L", lambda p, m, s: (p, m, s[:32] + (
            int.from_bytes(s[32:], "little") + L_ORDER).to_bytes(
                32, "little"))),
        ("off_curve_pubkey", lambda p, m, s: (off_curve_pubkey(), m, s)),
        ("noncanonical_R_y_ge_p", lambda p, m, s: (
            p, m, (p255 + 1).to_bytes(32, "little") + s[32:])),
        ("identity_pubkey_canonical",
         lambda p, m, s: (identity, m, small_order_sig())),
        ("identity_pubkey_y_ge_p", lambda p, m, s: (
            (p255 + 1).to_bytes(32, "little"), m, small_order_sig())),
        ("identity_pubkey_x0_sign_bit",
         lambda p, m, s: (with_sign(identity), m, small_order_sig())),
        ("minus_identity_x0_sign_bit", lambda p, m, s: (
            with_sign((p255 - 1).to_bytes(32, "little")), m,
            small_order_sig())),
    ]
    lanes = sorted(rng.sample(range(len(items)), 2 * len(cases)))
    return {lane: (cases[i % len(cases)][0],
                   cases[i % len(cases)][1](*items[lane]))
            for i, lane in enumerate(lanes)}


def leg_a(seed: int) -> dict:
    from benchmark.chain import LiteChain
    from tendermint_tpu.models.verifier import BATCH_CHUNK, default_verifier
    from tendermint_tpu.types.keys import verify_any

    rng = random.Random(f"{seed}/A")
    # one full chunk of genuine triples, signed on the host
    chain = LiteChain(seed, BATCH_CHUNK // C_VALIDATORS, C_VALIDATORS,
                      sign="host")
    items = [(pub, msg, chain.sigs[i * C_VALIDATORS + j])
             for i, msg in enumerate(chain.msgs)
             for j, pub in enumerate(chain.pubkeys)]
    lanes = lenient_lanes(items, rng)
    for lane, (_case, item) in lanes.items():
        items[lane] = item

    verifier = default_verifier()
    stats0 = dict(verifier.stats)
    t0 = time.perf_counter()
    got = verifier.verify(items)
    verify_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    oracle = [verify_any(*it) for it in items]
    oracle_s = time.perf_counter() - t0
    diff = [i for i in range(len(items)) if bool(got[i]) != oracle[i]]
    require(not diff, f"device and scalar oracle disagree on lanes {diff}: "
            f"{[(i, lanes.get(i, ('untampered',))[0]) for i in diff]}")
    by_case: dict = {}
    for lane, (case, _item) in lanes.items():
        by_case.setdefault(case, []).append(bool(got[lane]))
    # what RFC 8032 settles is held to it; on the encodings it leaves
    # open (y >= p, small-order keys) the oracle's verdict is the rule,
    # and the lane-by-lane comparison above is the check
    for case in ("s_plus_L", "off_curve_pubkey",
                 "identity_pubkey_x0_sign_bit",
                 "minus_identity_x0_sign_bit"):
        require(not any(by_case[case]), f"{case} accepted: {by_case}")
    require(all(oracle[i] for i in range(len(items)) if i not in lanes),
            "untampered lane rejected")

    stats = delta(dict(verifier.stats), stats0)
    require(stats["sigs"] == stats["jax_sigs"] == len(items) == BATCH_CHUNK,
            f"leg A signatures not all on the device: {stats}")
    return {
        "config": "one verifier chunk of lenient and non-canonical lanes "
                  "(the commit itself: cell commit_10kv.verify_commit)",
        "lanes": len(items), "reduced": {},
        "verifier": {"backend": verifier.backend,
                     "mesh_devices": verifier.mesh_devices,
                     "stats_delta": stats},
        "tampered_lanes": {str(k): v[0] for k, v in lanes.items()},
        "tampered_verdicts_by_case": by_case,
        "oracle": "types/keys.verify_any (OpenSSL + RFC 8032 reference)",
        "smoke_seconds": {"verify_incl_compiles": round(verify_s, 3),
                          "scalar_oracle": round(oracle_s, 2)},
    }


# ---------------------------------------------------------------- leg B

def sync_chains(seed: int, n_vals: int, n_txs: int, n_blocks: int,
                forged_blocks: int, forged_at: int):
    """(genesis, wire, expect, forged wire): `n_blocks` blocks and the
    sentinel that lends its LastCommit, each with its (hash, header app
    hash); and the first `forged_blocks` + 1 of them again with one
    forged precommit in the commit FOR block `forged_at`."""
    from benchmark.chain import ChainBuilder, forge_precommit

    builder = ChainBuilder(seed, n_vals, n_txs, B_TX_BYTES)
    wire, expect = builder.build_wire(n_blocks)
    sentinel, after = builder.build_wire(1, with_txs=False)
    fwire = wire[:forged_blocks + 1]
    fwire[forged_at] = forge_precommit(fwire[forged_at], seed)
    return builder.gen, wire + sentinel, expect + after, fwire


def leg_b(seed: int) -> dict:
    from benchmark.drivers.sync import PEER_ID, drive, fresh_reactor
    from benchmark.spans import SpanLog
    from tendermint_tpu.models.verifier import BatchVerifier

    t0 = time.perf_counter()
    gen, wire, expect, fwire = sync_chains(
        seed, B_VALIDATORS, B_TXS, B_BLOCKS, B_FORGED_BLOCKS, B_FORGED_AT)
    build_s = time.perf_counter() - t0

    verifier = BatchVerifier("auto")
    reactor = fresh_reactor(gen, verifier, B_WINDOW)
    sync_s = drive(reactor, wire, SpanLog())
    reactor.stop()
    n_sigs = B_BLOCKS * B_VALIDATORS
    require(reactor.state.last_block_height == B_BLOCKS ==
            reactor.block_store.height(),
            f"synced to {reactor.state.last_block_height}, store "
            f"{reactor.block_store.height()}, want {B_BLOCKS}")
    # the builder's serial apply: block h+1's header carries the app
    # hash after block h, and every stored block is the builder's own
    require(reactor.state.app_hash == expect[B_BLOCKS][1],
            "final app hash differs from the builder's serial apply")
    for h, (block_hash, app_hash) in enumerate(expect[:B_BLOCKS], 1):
        meta = reactor.block_store.load_block_meta(h)
        require(meta.block_id.hash == block_hash and
                meta.header.app_hash == app_hash,
                f"stored block {h} differs")
    stats = dict(verifier.stats)
    require(stats["jax_sigs"] == stats["sigs"] == n_sigs,
            f"commit signatures {n_sigs}, verifier saw {stats}")

    # the chain's first blocks again, one precommit of the commit FOR
    # block B_FORGED_AT forged
    fverifier = BatchVerifier("auto")
    freactor = fresh_reactor(gen, fverifier, B_WINDOW)
    drive(freactor, fwire, SpanLog())
    freactor.stop()
    require(freactor.state.last_block_height == B_FORGED_AT - 1 ==
            freactor.block_store.height(),
            f"forged commit for block {B_FORGED_AT}: applied up to "
            f"{freactor.state.last_block_height}")
    require({p for p, _ in freactor.switch.stopped} == {PEER_ID} and
            PEER_ID not in freactor.pool.peers,
            f"serving peer not punished: {freactor.switch.stopped}")
    return {
        "config": "BASELINE.json configs[3]: fast-sync replay, "
                  "64 validators, 5000-tx blocks",
        "validators": B_VALIDATORS, "txs_per_block": B_TXS,
        "tx_bytes": B_TX_BYTES,
        "verify_window": B_WINDOW, "blocks": B_BLOCKS,
        "reduced": {"blocks": f"{B_BLOCKS} of upstream's 50000"},
        "commit_signatures": n_sigs,
        "verifier": {"backend": verifier.backend,
                     "mesh_devices": verifier.mesh_devices,
                     "stats": stats},
        "app_hash": reactor.state.app_hash.hex(),
        "forged": {"blocks": B_FORGED_BLOCKS,
                   "forged_commit_for_block": B_FORGED_AT,
                   "applied": freactor.state.last_block_height,
                   "punished": freactor.switch.stopped,
                   "verifier_stats": dict(fverifier.stats)},
        "smoke_seconds": {"build_chain": round(build_s, 2),
                          "sync": round(sync_s, 3)},
    }


# ---------------------------------------------------------------- leg C

def lite_chains(seed: int, n_headers: int, n_vals: int, forged_headers: int,
                forged_at: int, sign: str = "device"):
    """(chain, forged wire): the benchmark's LiteChain, every precommit
    signed by ops/ed25519.sign_batch; and its first `forged_headers`
    signed headers again with a header nobody signed at `forged_at`."""
    from benchmark.chain import LiteChain

    chain = LiteChain(seed, n_headers, n_vals, sign=sign)
    fwire = chain.wire[:forged_headers]
    fwire[forged_at - 1] = chain.forged_header(forged_at)
    return chain, fwire


def leg_c(seed: int) -> dict:
    from benchmark.kvref import openssl_signer
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain,
                                               default_window)
    from tendermint_tpu.models.verifier import default_verifier

    t0 = time.perf_counter()
    chain, fwire = lite_chains(seed, C_HEADERS, C_VALIDATORS,
                               C_FORGED_HEADERS, C_FORGED_AT)
    build_s = time.perf_counter() - t0
    # a sample of what _sign_kernel made, against OpenSSL's
    n_sigs = C_HEADERS * C_VALIDATORS
    sample = range(0, n_sigs, n_sigs // 256)
    keys = [openssl_signer(s) for s in chain.seeds]
    bad = [i for i in sample if chain.sigs[i] !=
           keys[i % C_VALIDATORS].sign(chain.msgs[i // C_VALIDATORS])]
    require(not bad, f"device signatures differ from OpenSSL at {bad}")

    verifier = default_verifier()
    stats0 = dict(verifier.stats)
    valset, fcs = chain.decode()
    t0 = time.perf_counter()
    certify_chain(chain.chain_id, fcs, trusted=valset)
    certify_s = time.perf_counter() - t0
    stats = delta(dict(verifier.stats), stats0)
    require(stats["sigs"] == stats["jax_sigs"] == n_sigs,
            f"lite chain signatures not all on the device: {stats}")

    # a header nobody signed, dressed in the genuine commit's signatures
    valset, forged = chain.decode(fwire)
    try:
        certify_chain(chain.chain_id, forged, trusted=valset)
    except CertificationError as e:
        rejected = str(e)
    else:
        raise AssertionError("forged header certified")
    require(rejected.startswith(f"height {C_FORGED_AT}:"),
            f"forged header {C_FORGED_AT} rejected elsewhere: {rejected}")
    return {
        "config": "BASELINE.json configs[4]: lite-client chain "
                  "certification, 64 validators",
        "headers": C_HEADERS, "validators": C_VALIDATORS,
        "reduced": {"headers": f"{C_HEADERS} of upstream's 1000000"},
        "signatures": n_sigs, "signed_by": "ops/ed25519.sign_batch",
        "openssl_sample": {"compared": len(sample), "different": 0},
        "certify_window_headers": default_window(C_VALIDATORS),
        "verifier": {"backend": verifier.backend,
                     "mesh_devices": verifier.mesh_devices,
                     "stats_delta": stats},
        "forged": {"headers": C_FORGED_HEADERS, "forged_height": C_FORGED_AT,
                   "rejected_with": rejected},
        "smoke_seconds": {"build_and_sign_incl_compile": round(build_s, 2),
                          "certify": round(certify_s, 3)},
    }


# ---------------------------------------------------------------- leg D

def merkle_plane_check(seed: int) -> dict:
    """The host-facing Merkle/SHA entry points in a process that holds
    the device, against hashlib: a 5,000-leaf tx root and a 1,024-row
    fixed-length SHA wave (the statetree's rehash shape)."""
    from tendermint_tpu.ops import merkle
    rng = random.Random(f"{seed}/D.merkle")
    txs = [b"k%d=v%d" % (i, rng.randrange(1 << 30)) for i in range(B_TXS)]
    require(merkle.root_host(txs) == spec_merkle_root(txs),
            "ops.merkle.root_host differs from the hashlib spec")
    wave = [rng.randbytes(65) for _ in range(1024)]
    # the device's SHA plane is this leg's subject: no wave of the
    # program goes there (PR 35: the native kernel won at every size)
    t0 = time.perf_counter()
    got = merkle.sha256_many_device(wave)
    first_s = time.perf_counter() - t0
    require(got == [hashlib.sha256(p).digest() for p in wave],
            "ops.merkle.sha256_many_device differs from hashlib")
    require(merkle.sha256_many_host(wave) == got,
            "ops.merkle.sha256_many_host differs from hashlib")
    t0 = time.perf_counter()
    merkle.sha256_many_device([rng.randbytes(65) for _ in range(700)])
    return {"tx_root_leaves": len(txs), "sha_wave_rows": [1024, 700],
            "smoke_seconds": {
                "sha_wave_first_incl_compile": round(first_s, 3),
                "sha_wave_same_bucket": round(time.perf_counter() - t0, 3)}}


def _wait(cond, what: str, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"leg D: timed out waiting for {what}")
        time.sleep(0.05)


def leg_d(seed: int) -> dict:
    from tendermint_tpu.config import default_config
    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.node import Node
    from tendermint_tpu.rpc.client import JSONRPCClient
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)

    merkle_check = merkle_plane_check(seed)
    rng = random.Random(f"{seed}/D")
    keys = [PrivKey.generate(rng.randbytes(32)) for _ in range(D_NODES)]
    gen = GenesisDoc(chain_id=f"smoke-net-{seed}", genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519, 10)
                                 for k in keys])
    verifier = default_verifier()
    stats0 = dict(verifier.stats)
    home = tempfile.mkdtemp(prefix="chip-smoke-")
    nodes = []
    t0 = time.perf_counter()
    try:
        for i, key in enumerate(keys):
            cfg = default_config(os.path.join(home, f"node{i}"))
            cfg.p2p.laddr = "tcp://127.0.0.1:0"
            cfg.p2p.addr_book_strict = False
            cfg.rpc.laddr = "tcp://127.0.0.1:0"
            nodes.append(Node(cfg, gen,
                              priv_validator=PrivValidator(LocalSigner(key)),
                              in_memory=True, with_p2p=True,
                              with_rpc=i < D_RPC_NODES))
        require(all(n.verifier is verifier for n in nodes),
                "nodes do not share the process verifier")
        for node in nodes:
            node.start()
        for i, node in enumerate(nodes):
            for other in nodes[:i]:
                node.switch.dial_peer(other.switch.listen_address)
        clients = [JSONRPCClient("http://%s:%d" % n.rpc_address)
                   for n in nodes[:D_RPC_NODES]]
        _wait(lambda: all(n.height >= 1 for n in nodes), "first block")
        status = clients[0].call("status")
        require(status["latest_block_height"] >= 1, f"status: {status}")
        boot_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        last_height = 0
        for i in range(D_WRITES):
            key, val = b"smoke%d-%d" % (seed, i), b"v%d" % rng.randrange(
                1 << 30)
            writer, reader = i % D_RPC_NODES, (i + 1) % D_RPC_NODES
            res = clients[writer].call("broadcast_tx_commit",
                                       tx=key + b"=" + val)
            require(res["deliver_tx"]["code"] == 0, f"write {i}: {res}")
            last_height = res["height"]
            # acknowledged at res["height"]: any node that has reached
            # that height must serve it
            _wait(lambda: nodes[reader].height >= last_height,
                  f"node{reader} to reach height {last_height}")
            got = clients[reader].call("abci_query", path="/store",
                                       data=key)
            require(bytes.fromhex(got["response"]["value"]) == val,
                    f"write {i} acknowledged at height {last_height} by "
                    f"node{writer} not read back from node{reader}: {got}")
        writes_s = time.perf_counter() - t0

        # block last_height+1 carries the app hash after the last write
        agree_at = last_height + 1
        _wait(lambda: all(n.height >= agree_at for n in nodes),
              f"all nodes at height {agree_at}")
        metas = [n.block_store.load_block_meta(agree_at) for n in nodes]
        require(len({m.block_id.hash for m in metas}) == 1 and
                len({m.header.app_hash for m in metas}) == 1,
                f"nodes disagree at height {agree_at}")
        heights = [n.height for n in nodes]
    finally:
        for node in nodes:
            node.stop()
        shutil.rmtree(home, ignore_errors=True)
    stats = delta(dict(verifier.stats), stats0)
    return {
        "config": "BASELINE.json configs[0]: 4-validator net, kvstore app",
        "nodes": D_NODES, "rpc_nodes": D_RPC_NODES,
        "transport": "loopback TCP, one process",
        "timeouts": "upstream defaults (config.default_config)",
        "reduced": {},
        "writes": D_WRITES, "read_back_from_another_node": D_WRITES,
        "agreed": {"height": agree_at,
                   "block_hash": metas[0].block_id.hash.hex(),
                   "app_hash": metas[0].header.app_hash.hex(),
                   "node_heights_at_check": heights},
        "verifier": {
            "backend": verifier.backend,
            "auto_threshold": verifier.auto_threshold,
            "stats_delta": stats,
            "signatures_total": stats["sigs"],
            "signatures_on_device": stats["jax_sigs"],
            "signatures_on_host": stats["sigs"] - stats["jax_sigs"]},
        "merkle_plane_check": merkle_check,
        "smoke_seconds": {"boot_to_first_block": round(boot_s, 2),
                          "writes_and_reads": round(writes_s, 2)},
    }


# ----------------------------------------------------------------- main

def native_check() -> dict:
    from tendermint_tpu import native
    status = native.status()    # builds and loads; a failure raises
    missing = [name for name, s in status.items() if not s["loaded"]]
    require(not missing, f"native extensions not loaded: {missing} "
            "(no C++ toolchain, or TM_TPU_NO_NATIVE set)")
    require(native.aead_available(),
            "native AEAD kernels failed their RFC 8439 self-check")
    return {"extensions": status, "available": native.available(),
            "aead_self_check": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive the system's main paths once on the chip.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every key, transaction and tamper choice")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if any(d.platform != "tpu" for d in devs):
        found = sorted({f"{d.platform} ({d.device_kind})" for d in devs})
        print(f"chip_smoke: needs a TPU on every device; JAX found "
              f"{len(devs)} device(s): {', '.join(found)} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
              f"Nothing was built or run.", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from tendermint_tpu import telemetry
    from tendermint_tpu.ops import ed25519
    from tendermint_tpu.utils import compile_cache
    from tendermint_tpu.utils.log import setup_logging
    import jaxlib
    import libtpu
    setup_logging("error")      # four nodes at info drown the records
    telemetry.configure(enabled=True)
    log = CompileLog()
    say("start", device=device, seed=args.seed,
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": libtpu.__version__},
        compile_cache_dir=compile_cache.enable(),
        note="every time printed is smoke output, not a benchmark number")
    say("native", **native_check())

    t_all = time.perf_counter()
    for name, leg in (("A", leg_a), ("B", leg_b), ("C", leg_c),
                      ("D", leg_d)):
        k0, m0, c0 = kernel_counts(), merkle_counts(), dict(log.cache)
        t0 = time.perf_counter()
        record = leg(args.seed)
        kernels = delta(kernel_counts(), k0)
        if name != "D":
            require_pallas_only(name, kernels)
        say("leg", leg=name, ok=True, kernels=kernels,
            merkle_sha=delta(merkle_counts(), m0),
            persistent_cache=delta(log.cache, c0),
            smoke_leg_seconds=round(time.perf_counter() - t0, 2), **record)

    memory = [{"id": d.id, "peak_bytes_in_use":
               d.memory_stats()["peak_bytes_in_use"]} for d in devs]
    require(all(m["peak_bytes_in_use"] for m in memory),
            f"a device did no work: {memory}")
    say("compiles",
        per_shape=log.table(ed25519.predecomp_stats()["first_call_s"]),
        persistent_cache=log.cache, device_memory=memory,
        smoke_total_seconds=round(time.perf_counter() - t_all, 2))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
