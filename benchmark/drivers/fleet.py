"""Driver `fleet`: clients of a served net.

The net is chip_smoke.py leg D's: complete Nodes in THIS process (a
chip belongs to one process) on loopback TCP, some with RPC, in-memory
stores, the KVStore app, every timeout written out in the
configuration file. The load comes from a child process that never
imports JAX (benchmark/loadgen.py): open-loop writes through
`broadcast_tx_sync`, commits learned from the Tx and NewBlock events of
subscriptions, because `broadcast_tx_commit` holds one of the front
door's few worker threads for the whole of a commit.

Set-up boots the net, compiles the one program the audit needs on a
toy chain of the same shape, starts the load and lets the net warm
under it for a fixed number of blocks. The window is `--seconds` of the
cell's load; a fixed drain follows under the same load.

`correct`, after the drain: every window write that was not refused is
in the block log; sampled acknowledged writes are in node A's block log at the
acknowledged height and read back from the OTHER RPC node with the
value a plain dict replay of that log gives; the app hash carried by
every header equals kvref.PlainKV's; all nodes agree on block and app
hash at the last common height; and a lite audit of the committed
chain's last heights on the device certifies them and rejects a forged
header at its own height.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from benchmark import probe
from benchmark.chain import chain_id_of
from benchmark.harness import Outcome
from benchmark.kvref import PlainKV
from benchmark.loadgen import rpc_call
from benchmark.stats import percentile, quartile_spread

TIMEOUT_KEYS = ("timeout_propose", "timeout_propose_delta",
                "timeout_prevote", "timeout_prevote_delta",
                "timeout_precommit", "timeout_precommit_delta",
                "timeout_commit", "skip_timeout_commit")


def _wait(cond, what: str, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.02)


def boot_net(h, home: str):
    """(nodes, genesis): the configuration's validators, each a
    complete Node, started and dialled into a full mesh."""
    from tendermint_tpu.config import default_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)
    p = h.params
    rng = random.Random(f"{h.seed}/net/keys")
    keys = [PrivKey.generate(rng.randbytes(32))
            for _ in range(int(p["validators"]))]
    gen = GenesisDoc(chain_id=chain_id_of("net", h.seed), genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519, 10)
                                 for k in keys])
    nodes = []
    for i, key in enumerate(keys):
        cfg = default_config(os.path.join(home, f"node{i}"))
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.addr_book_strict = False
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        for k in TIMEOUT_KEYS:
            setattr(cfg.consensus, k, p["consensus"][k])
        if h.trace:
            # stage stamps are read in the traced run only
            cfg.base.slo, cfg.base.slo_sample = "on", float(
                p.get("slo_sample", 0.25))
        nodes.append(Node(cfg, gen,
                          priv_validator=PrivValidator(LocalSigner(key)),
                          in_memory=True, with_p2p=True,
                          with_rpc=i < int(p["rpc_nodes"])))
    for node in nodes:
        node.start()
    for i, node in enumerate(nodes):
        for other in nodes[:i]:
            node.switch.dial_peer(other.switch.listen_address)
    return nodes, gen


def audit_chain(nodes, gen, heights, forged_pos: int):
    """[FullCommit] for `heights` of node 0's committed chain, with a
    header nobody signed at position `forged_pos`."""
    from tendermint_tpu.lite.types import FullCommit, SignedHeader
    from tendermint_tpu.types.block import BlockID, Commit, Header
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote
    store = nodes[0].block_store
    valset = ValidatorSet([Validator(v.pubkey, v.power)
                           for v in gen.validators])
    fcs = []
    for k, height in enumerate(heights):
        meta = store.load_block_meta(height)
        commit = store.load_seen_commit(height)
        header, bid = meta.header, meta.block_id
        if k == forged_pos:
            header = Header.from_obj(dict(header.to_obj(),
                                          app_hash=(b"\xff" * 32).hex()))
            bid = BlockID(header.hash(), bid.parts)
            commit = Commit(bid, [
                None if v is None else Vote(
                    v.validator_address, v.validator_index, v.height,
                    v.round, v.timestamp_ns, v.type, bid, v.signature)
                for v in commit.precommits])
        fcs.append(FullCommit(SignedHeader(header, commit, bid), valset))
    return valset, fcs


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.telemetry import slo

    p = h.params
    rate, drain_s = float(p["rate"]), float(p["drain_s"])
    drain_max_s = float(p.get("drain_max_s", drain_s))
    n_audit = int(p["audit_heights"])
    telemetry.configure(enabled=h.trace)
    rng = random.Random(f"{h.seed}/fleet")
    verifier = default_verifier()
    c0 = probe.counters(verifier)
    home = tempfile.mkdtemp(prefix="bench-net-")
    report_path = os.path.join(home, "loadgen.json")
    nodes, child = [], None
    seen = []       # (height, perf_counter when node 0 first showed it)

    def watch_heights(stop):
        last = 0
        while not stop.is_set():
            now_h = nodes[0].height
            if now_h > last:
                seen.append((now_h, time.perf_counter()))
                last = now_h
            time.sleep(0.01)

    stop_watch = threading.Event()
    try:
        with h.spans.span("boot_net"):
            nodes, gen = boot_net(h, home)
            if any(n.verifier is not verifier for n in nodes):
                raise RuntimeError("nodes do not share the process verifier")
            _wait(lambda: all(n.height >= 1 for n in nodes), "first block")
        if not h.rehearsal:
            # the audit's one program, compiled in set-up on a toy
            # chain of the audit's own shape
            with h.spans.span("warm_audit"):
                from benchmark.chain import LiteChain
                toy = LiteChain(h.seed, n_audit, len(nodes), sign="openssl")
                valset, fcs = toy.decode()
                certify_chain(toy.chain_id, fcs, trusted=valset)
                del toy, valset, fcs
        targets = [list(n.rpc_address) for n in nodes[:int(p["rpc_nodes"])]]
        child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", json.dumps({
                "targets": targets, "rate": rate, "seed": h.seed,
                "tx_bytes": int(p["tx_bytes"]),
                "keyspace": int(p["keyspace"]), "conns": int(p["conns"]),
                "method": p["method"], "subscribe": True,
                "out": report_path})],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=h.root,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0"))
        ready = child.stdout.readline()
        if not ready:
            raise RuntimeError("the load generator did not start")
        threading.Thread(target=watch_heights, args=(stop_watch,),
                         daemon=True, name="bench-heights").start()
        warm_from = nodes[0].height
        with h.spans.span("warm_under_load"):
            _wait(lambda: nodes[0].height >= warm_from +
                  int(p["warm_blocks"]), "the warm blocks")
        h.settle()
        slo.reset()

        # ---- the window
        backlog0 = [n.mempool.size() for n in nodes]
        t_open_mono = time.monotonic() + 0.25
        child.stdin.write((json.dumps(
            {"open": t_open_mono, "seconds": h.seconds, "drain": drain_s,
             "drain_max": drain_max_s}) + "\n").encode())
        child.stdin.flush()
        time.sleep(max(0.0, t_open_mono - time.monotonic()))
        t0 = h.open_window()
        h0 = nodes[0].height
        trace_s = float(p.get("trace_seconds", 8.0))
        if h.trace and not h.rehearsal:
            time.sleep(min(2.0, h.seconds / 4))
            with h.profile(), h.spans.span("fleet_window"):
                time.sleep(min(trace_s, h.seconds / 2))
        time.sleep(max(0.0, t0 + h.seconds - time.perf_counter()))
        t1 = h.close_window()
        h1 = nodes[0].height
        backlog1 = [n.mempool.size() for n in nodes]
        slo_doc = slo.snapshot(windows=False) if h.trace else None
        child.wait(timeout=drain_max_s + 60.0)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exit {child.returncode}")
        with open(report_path) as f:
            report = json.load(f)
        stop_watch.set()

        # ---- client side
        writes = report["window"]
        refused = [w for w in writes if w["refused"]]
        failed = len(refused) + sum(
            1 for w in writes if not w["refused"] and w["commit_ms"] is None)
        commit_ms = [w["commit_ms"] for w in writes
                     if w["commit_ms"] is not None]
        client = {
            "commit_ms": commit_ms,
            "check_ms": [w["check_ms"] for w in writes
                         if w["check_ms"] is not None],
            "late_ms": [w["late_ms"] for w in writes],
            "offered": report["offered"], "events": report["events"],
            "learned_from": report["learned_from"],
            "refused": len(refused), "acked": len(commit_ms),
            "backlog_open": backlog0, "backlog_close": backlog1,
        }
        # blocks of the window: those node 0 first showed inside it
        inside = [(hh, at) for hh, at in seen if t0 <= at <= t1]
        store = nodes[0].block_store
        metas = {hh: store.load_block_meta(hh) for hh, _ in inside}
        client["blocks"] = [
            {"height": hh, "seen_at": at - t0,
             "txs": metas[hh].header.num_txs,
             "round": store.load_seen_commit(hh).round()}
            for hh, at in inside]
        if slo_doc and slo_doc.get("enabled"):
            client["slo_stages"] = slo_doc.get("stages", {})
        gaps = [(b["seen_at"] - a["seen_at"], a["seen_at"]) for a, b in
                zip(client["blocks"], client["blocks"][1:])]
        worst_late = max(writes, key=lambda w: w["late_ms"], default=None)
        h.note("stalls", longest_block_gaps=sorted(gaps, reverse=True)[:3],
               block_gap_spread=quartile_spread([g for g, _ in gaps]),
               late_ms_max=worst_late and worst_late["late_ms"],
               late_max_at_s=worst_late and
               worst_late["due"] - report["opened"]["open"],
               late_p50=percentile(client["late_ms"], 0.5),
               check_p50=percentile(client["check_ms"], 0.5),
               check_p99=percentile(client["check_ms"], 0.99))
        h.note("window", heights=[h0, h1], offered=report["offered"],
               acked=len(commit_ms), refused=len(refused), failed=failed,
               backlog_open=backlog0, backlog_close=backlog1,
               p50=percentile(commit_ms, 0.5), p95=percentile(commit_ms, 0.95),
               late_p99=percentile(client["late_ms"], 0.99),
               blocks=len(inside), events=report["events"],
               learned_from=report["learned_from"],
               refused_why=sorted({w["refused"] for w in refused})[:5])

        # ---- what the window produced, against the plain reference
        # a refusal is the front door's admission control at work (seen
        # on the chip when a 6 s gap between blocks filled its queue): it
        # counts in `failed`, and is no broken guarantee
        # the chain is quiet once every mempool is empty and all nodes
        # have since committed two empty blocks; only then is there a
        # last height whose state the reads below can be held to
        deadline = time.monotonic() + float(p.get("settle_s", 60.0))
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError("the chain did not go quiet")
            if any(n.mempool.size() for n in nodes):
                time.sleep(0.05)
                continue
            base = max(n.height for n in nodes)
            _wait(lambda: all(n.height >= base + 2 for n in nodes),
                  "two more blocks")
            store = nodes[0].block_store
            if not any(n.mempool.size() for n in nodes) and not any(
                    store.load_block_meta(hh).header.num_txs
                    for hh in (base + 1, base + 2)):
                break
        # block top + 1 carries the app hash after block top
        top = base + 1
        a_host, a_port = targets[0]
        b_host, b_port = targets[-1]
        ref, log, final = PlainKV(), {}, {}
        app_bad = 0
        for height in range(1, top + 1):
            blk = rpc_call(a_host, a_port, "block", height=height)["block"]
            txs = [bytes.fromhex(t) for t in blk["data"]["txs"]]
            for i, tx in enumerate(txs):
                k, _, v = tx.partition(b"=")
                final[k] = v
                log[(k, v)] = (height, i)
            after = ref.apply_block(txs)
            carried = nodes[0].block_store.load_block_meta(
                height + 1).header.app_hash
            if ref.store and carried != after:
                app_bad += 1
        h.check("app_hashes_differing_from_plain_reference", app_bad, 0)
        h.check("window_writes_never_committed", sum(
            1 for w in writes if not w["refused"] and
            (w["key"].encode(), w["value"].encode("latin-1")) not in log), 0)
        acked = [w for w in writes if w["commit_ms"] is not None]
        sample = rng.sample(acked, min(int(p["readback_sample"]), len(acked)))
        if acked:
            sample.append(max(acked, key=lambda w: (w["height"], w["index"])))
        not_in_log = wrong = 0
        for w in sample:
            k, v = w["key"].encode(), w["value"].encode("latin-1")
            if log.get((k, v), (None,))[0] != w["height"]:
                not_in_log += 1
            # written through one RPC node, read back from the other
            host, port = (b_host, b_port) if w["target"] == 0 \
                else (a_host, a_port)
            got = rpc_call(host, port, "abci_query", path="/store",
                           data=k.hex())
            if bytes.fromhex(got["response"]["value"] or "") != final.get(k):
                wrong += 1
                h.note("read_back_differs", key=w["key"], acked_at=w["height"],
                       want=(final.get(k) or b"")[:24].decode("latin-1"),
                       got=bytes.fromhex(got["response"]["value"] or "")[:24]
                       .decode("latin-1"), top=top)
        h.check("acknowledged_writes_missing_from_log", not_in_log, 0)
        h.check("read_backs_differing_from_plain_reference", wrong, 0)
        ms = [n.block_store.load_block_meta(top) for n in nodes]
        h.check("nodes_disagreeing_at_last_height",
                len({m.block_id.hash for m in ms}) +
                len({m.header.app_hash for m in ms}) - 2, 0)

        # ---- the device: a lite audit of the committed chain
        _wait(lambda: min(n.height for n in nodes) > n_audit,
              f"a chain of {n_audit} blocks to audit", 180.0)
        heights = list(range(top - n_audit + 1, top + 1)) if top >= n_audit \
            else list(range(1, n_audit + 1))
        forged_pos = rng.randrange(n_audit // 2, n_audit)
        valset, fcs = audit_chain(nodes, gen, heights, forged_pos)
        with probe.VerifierTap(verifier, h.spans, p.get("control")):
            with h.profile(), h.spans.span("lite_audit"):
                try:
                    certify_chain(gen.chain_id, fcs, trusted=valset)
                    where = "certified"
                except CertificationError as e:
                    where = str(e)
        h.note("lite_audit", heights=[heights[0], heights[-1]],
               forged_height=heights[forged_pos], outcome=where[:120])
        h.check("audit_forged_header_not_rejected_at_its_height",
                0 if where.startswith(f"height {heights[forged_pos]}:")
                else 1, 0)
        counters = probe.delta(probe.counters(verifier), c0)
        if not h.rehearsal:
            h.check("audit_signatures_off_device",
                    max(0, sum(1 for f in fcs for v in
                               f.signed_header.commit.precommits
                               if v is not None) -
                        counters["verifier.jax_sigs"]), 0)
    finally:
        stop_watch.set()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait(timeout=10.0)
        stoppers = [threading.Thread(target=node.stop, name=f"bench-stop-{i}")
                    for i, node in enumerate(nodes)]
        for t in stoppers:
            t.start()
        for t in stoppers:
            t.join(timeout=60.0)
        shutil.rmtree(home, ignore_errors=True)
    client["window_s"] = h.seconds      # the client's window: by due time
    return Outcome(attempted=len(writes), failed=failed, counters=counters,
                   client=client)
