"""Driver `fleet_ycsb`: YCSB workload A against a proof-carrying store.

The net is the `fleet` driver's (complete Nodes in THIS process on
loopback TCP, some with RPC, in-memory stores, the KVStore app, every
timeout written out), with two things the genesis says and `fleet`'s
has no place for, which is why the boot is written here and not
imported: `app_state.kvstore.commit_backend` (the authenticated state
tree) and `app_state.kvstore.records`, a file of `recordcount` records
of `record_bytes` made from the seed, which every validator loads at
InitChain. The load comes from benchmark/ycsbgen.py's child: half
proven reads (`abci_query prove=true height=0`), half updates
(`broadcast_tx_sync`), keys Zipfian over the loaded records.

**The run's clock** is `fleet_procs`': a budget a phase, printed as a
`{"bench": "clock"}` line, every wait bounded by what is left of its
phase, and one deadline at `deadline_s` that stops the nodes and exits
5.

`correct`, outside the window, at the timed sizes, every limit 0,
against benchmark/treeref.py (hashlib; nothing of the program): the app
hash carried by every header from block 1 on equals the reference's
replay of the block log over the loaded records; every read of the
window verifies under `treeref.verify` against the app hash of the
header after the version it names, and returns the value the replay
holds at that version; every window update that was not refused is in
the log; sampled acknowledged updates are in the log at their height
and are read back, proven, from the OTHER RPC node at a version no
older than that height, the proof anchored
at a header the lite audit certifies on the device (one of its 64
heights, before the forged one); all nodes agree at the last height;
the audit rejects a forged header at its height. Two controls
read above 0 on every seed: a proof with one sibling flipped and a
proof given another value are each rejected, by the program's
`statetree.verify` and by `treeref.verify` alike.
"""

from __future__ import annotations

import bisect
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from types import SimpleNamespace

from benchmark import (probe, program_spans, treeref, ycsb,
                       ycsb_spans)
from benchmark.chain import chain_id_of
from benchmark.drivers.fleet import TIMEOUT_KEYS, audit_chain
from benchmark.drivers.fleet_procs import Overdue, RunClock
from benchmark.harness import Outcome
from benchmark.loadgen import rpc_call
from benchmark.stats import percentile, quartile_spread


AUDIT_TAIL = 8      # heights of the audit past the last read-back's anchor


class Clock(RunClock):
    """fleet_procs' clock, with the phases the traffic file budgets
    beyond that driver's own."""

    def __init__(self, t_start, deadline_s, budget_s, limit_s=None, **kw):
        super().__init__(t_start, deadline_s, budget_s, limit_s, **kw)
        for name, seconds in budget_s.items():
            self.budget.setdefault(name, float(seconds))
            self.limit.setdefault(name, 2.0 * float(seconds))


def resident_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def boot_net(h, home: str, app_state: dict):
    """(nodes, genesis): `fleet.boot_net` with the genesis' app_state.
    Every Node's constructor runs InitChain, so the store is loaded
    when this returns."""
    from tendermint_tpu.config import default_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivKey
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)
    p = h.params
    rng = random.Random(f"{h.seed}/net/keys")
    keys = [PrivKey.generate(rng.randbytes(32))
            for _ in range(int(p["validators"]))]
    gen = GenesisDoc(chain_id=chain_id_of("ycsb", h.seed), genesis_time_ns=1,
                     validators=[GenesisValidator(k.pubkey.ed25519,
                                                  int(p["voting_power_each"]))
                                 for k in keys],
                     app_state=app_state)
    nodes = []
    for i, key in enumerate(keys):
        cfg = default_config(os.path.join(home, f"node{i}"))
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.addr_book_strict = False
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        for k in TIMEOUT_KEYS:
            setattr(cfg.consensus, k, p["consensus"][k])
        if h.trace:
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


def sha_batches() -> dict:
    from tendermint_tpu import telemetry
    return {impl: float(telemetry.value("merkle_sha_batches_total",
                                        {"impl": impl}) or 0.0)
            for impl in ("device", "native", "host")}


def flipped(proof: bytes) -> bytes:
    """The proof with one bit of its deepest sibling turned over."""
    doc = json.loads(proof)
    bit, sibling = doc["steps"][-1]
    raw = bytearray(bytes.fromhex(sibling))
    raw[0] ^= 1
    doc["steps"][-1] = [bit, raw.hex()]
    return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()


def both_reject(proof: bytes, key: bytes, value: bytes,
                app_hash: bytes) -> bool:
    """Whether the program's verifier and the reference's each refuse
    to bind `value` to `key` under `app_hash` by `proof`."""
    from tendermint_tpu import statetree
    try:
        statetree.verify(statetree.proof_from_bytes(proof), key, value,
                         app_hash)
        program = False
    except statetree.ProofError:
        program = True
    try:
        treeref.verify(proof, key, value, app_hash)
        plain = False
    except treeref.Rejected:
        plain = True
    return program and plain


def run(h) -> Outcome:
    # a program that cannot run this deployment (the parent commit under
    # this benchmark's files) fails here, at once
    from tendermint_tpu.abci.apps.kvstore import BACKENDS
    from tendermint_tpu.abci.apps.records import write_records
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.telemetry import slo

    p = h.params
    if p["commit_backend"] not in BACKENDS:
        raise ValueError("the deployment names a backend the program "
                         "does not have")
    rate, drain_s = float(p["rate"]), float(p["drain_s"])
    drain_max_s = float(p.get("drain_max_s", drain_s))
    n_audit = int(p["audit_heights"])
    n_records, record_bytes = int(p["recordcount"]), int(p["record_bytes"])
    home = tempfile.mkdtemp(prefix="bench-ycsb-")
    state = {"child": None}

    def stop_everything() -> None:
        """What the deadline does: no wait, no grace."""
        child = state["child"]
        if child is not None and child.poll() is None:
            child.kill()
        shutil.rmtree(home, ignore_errors=True)

    clock = Clock(h.t_start, float(p["deadline_s"]), p["budget_s"],
                  limit_s={"drain": drain_max_s + 5.0,
                           "window": h.seconds + 15.0, "settle": 30.0},
                  on_deadline=stop_everything)
    telemetry.configure(enabled=True)   # as every Node's own default
    rng = random.Random(f"{h.seed}/fleet")
    verifier = default_verifier()
    c0 = probe.counters(verifier)
    report_path = os.path.join(home, "loadgen.json")
    nodes: list = []
    seen = []       # (height, perf_counter when node 0 first showed it)
    stop_watch = threading.Event()

    def watch_heights():
        last = 0
        while not stop_watch.is_set():
            now_h = nodes[0].height
            if now_h > last:
                seen.append((now_h, time.perf_counter()))
                last = now_h
            time.sleep(0.01)

    try:
        with clock.phase("compile"), h.spans.span("warm_audit"):
            if not h.rehearsal:
                # the audit's one program, first called here on a toy
                # chain of the audit's own shape
                from benchmark.chain import LiteChain
                toy = LiteChain(h.seed, n_audit, int(p["validators"]),
                                sign="openssl")
                toy_valset, toy_fcs = toy.decode()
                certify_chain(toy.chain_id, toy_fcs, trusted=toy_valset)
                del toy, toy_valset, toy_fcs
        with clock.phase("records"), h.spans.span("write_records"):
            ycsb.distinct_keys(n_records)
            entry = write_records(
                os.path.join(home, "records.bin"),
                ycsb.records(h.seed, n_records, record_bytes))
            h.note("records", count=entry["count"], sha256=entry["sha256"],
                   file_bytes=os.path.getsize(entry["file"]))
        with clock.phase("load"), h.spans.span("boot_net"):
            rss0, t_load = resident_bytes(), time.perf_counter()
            sha0 = sha_batches()
            nodes, gen = boot_net(h, home, {"kvstore": {
                "commit_backend": p["commit_backend"], "records": entry}})
            load_s = time.perf_counter() - t_load
            rss1 = resident_bytes()
            if any(n.verifier is not verifier for n in nodes):
                raise RuntimeError("nodes do not share the process verifier")
            sha1 = sha_batches()
            # the program's own record of the loads, read while the ring
            # still holds it
            loads = program_spans.rows(SimpleNamespace(
                window=(t_load, time.perf_counter())), "tree.load")
            tree_loads = None if loads is None else [
                {"seconds": row["end"] - row["start"],
                 "records": row["args"].get("records", 0),
                 "bytes": row["args"].get("bytes", 0)} for row in loads]
            # where the load's SHA waves ran, by the program's counter
            h.note("load", validators=len(nodes), records=n_records,
                   boot_s=load_s, tree_loads=tree_loads,
                   sha_batches={k: sha1[k] - sha0[k] for k in sha1},
                   resident_MB_per_validator=(rss1 - rss0) / len(nodes) / 1e6)
            clock.wait(lambda: all(n.height >= 1 for n in nodes),
                       "the first block")

        with clock.phase("warm"), h.spans.span("warm_under_load"):
            targets = [list(n.rpc_address)
                       for n in nodes[:int(p["rpc_nodes"])]]
            child = state["child"] = subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.utils.procs",
                 sys.executable, "-m", "benchmark.ycsbgen", json.dumps({
                     "targets": targets, "rate": rate, "seed": h.seed,
                     "recordcount": n_records, "record_bytes": record_bytes,
                     "read_share": float(p["read_share"]),
                     "theta": float(p["zipfian_constant"]),
                     "conns": int(p["conns"]), "method": p["method"],
                     "subscribe": True, "out": report_path})],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=h.root,
                env=dict(os.environ, JAX_PLATFORMS="cpu",
                         PYTHONHASHSEED="0"))
            ready: list = []
            reader = threading.Thread(
                target=lambda: ready.append(child.stdout.readline()),
                daemon=True, name="bench-loadgen-ready")
            reader.start()
            reader.join(clock.left())
            if not ready or not ready[0]:
                raise Overdue("the load generator did not start")
            threading.Thread(target=watch_heights, daemon=True,
                             name="bench-heights").start()
            warm_from = nodes[0].height
            clock.wait(lambda: nodes[0].height >= warm_from +
                       int(p["warm_blocks"]), "the warm blocks")
            h.settle()
            slo.reset()

        # ---- the window
        with clock.phase("window"):
            backlog0 = [n.mempool.size() for n in nodes]
            t_open_mono = time.monotonic() + 0.25
            child.stdin.write((json.dumps(
                {"open": t_open_mono, "seconds": h.seconds,
                 "drain": drain_s, "drain_max": drain_max_s}) +
                "\n").encode())
            child.stdin.flush()
            time.sleep(max(0.0, t_open_mono - time.monotonic()))
            t0 = h.open_window()
            h0 = nodes[0].height
            # in a traced run the window's spans are taken out of the
            # program's ring as it goes (benchmark/ycsb_spans.py)
            harvest = ycsb_spans.Harvest(
                ("tree.commit", "app.query") if h.trace else ())

            def sleep_until(t_end: float) -> None:
                while True:
                    harvest.take(time.perf_counter())
                    left = t_end - time.perf_counter()
                    if left <= 0:
                        return
                    time.sleep(min(left, ycsb_spans.EVERY_S))

            trace_s = float(p.get("trace_seconds", 8.0))
            if h.trace and not h.rehearsal:
                sleep_until(t0 + min(2.0, h.seconds / 4))
                with h.profile(), h.spans.span("fleet_window"):
                    sleep_until(time.perf_counter() +
                                min(trace_s, h.seconds / 2))
            sleep_until(t0 + h.seconds)
            t1 = h.close_window()
            harvest.take(t1)
            h1 = nodes[0].height
            backlog1 = [n.mempool.size() for n in nodes]
            slo_doc = slo.snapshot(windows=False) if h.trace else None

        with clock.phase("drain"):
            try:
                child.wait(timeout=clock.left())
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait(timeout=5.0)
                raise Overdue(f"the load generator outlasted the drain's "
                              f"limit ({clock.report()})")
            if child.returncode != 0:
                raise RuntimeError(
                    f"load generator exit {child.returncode}")
            with open(report_path) as f:
                report = json.load(f)

        # ---- client side
        ops = report["window"]
        updates = [w for w in ops if w["kind"] == "update"]
        reads = [w for w in ops if w["kind"] == "read"]
        refused = [w for w in ops if w["refused"]]
        failed = len(refused) + sum(
            1 for w in ops if not w["refused"] and w["commit_ms"] is None)
        commit_ms = [w["commit_ms"] for w in updates
                     if w["commit_ms"] is not None]
        read_ms = [w["commit_ms"] for w in reads
                   if w["commit_ms"] is not None]
        client = {
            # `fleet`'s keys, over the window's updates
            "commit_ms": commit_ms,
            "check_ms": [w["check_ms"] for w in updates
                         if w["check_ms"] is not None],
            "late_ms": [w["late_ms"] for w in ops],
            "offered": report["offered"], "events": report["events"],
            "learned_from": report["learned_from"],
            "refused": len(refused), "acked": len(commit_ms),
            "backlog_open": backlog0, "backlog_close": backlog1,
            # this cell's own
            "read_ms": read_ms,
            "load_rss_bytes_per_validator": (rss1 - rss0) / len(nodes),
            "tree_loads": tree_loads,
            "span_rows": harvest.rows(),
        }
        inside = [(hh, at) for hh, at in seen if t0 <= at <= t1]
        store = nodes[0].block_store
        metas = {hh: store.load_block_meta(hh) for hh, _ in inside}
        client["blocks"] = [
            {"height": hh, "seen_at": at - t0,
             "txs": metas[hh].header.num_txs,
             "parts": metas[hh].block_id.parts.total,
             "round": store.load_seen_commit(hh).round()}
            for hh, at in inside]
        if slo_doc and slo_doc.get("enabled"):
            client["slo_stages"] = slo_doc.get("stages", {})
        gaps = [(b["seen_at"] - a["seen_at"], a["seen_at"]) for a, b in
                zip(client["blocks"], client["blocks"][1:])]
        h.note("stalls", longest_block_gaps=sorted(gaps, reverse=True)[:3],
               block_gap_spread=quartile_spread([g for g, _ in gaps]),
               late_ms_max=max((w["late_ms"] for w in ops), default=None),
               late_p50=percentile(client["late_ms"], 0.5),
               check_p50=percentile(client["check_ms"], 0.5),
               check_p99=percentile(client["check_ms"], 0.99))
        h.note("window", heights=[h0, h1], offered=report["offered"],
               reads=len(reads), updates=len(updates),
               acked=len(commit_ms), answered_reads=len(read_ms),
               refused=len(refused), failed=failed,
               backlog_open=backlog0, backlog_close=backlog1,
               p50=percentile(commit_ms, 0.5), p95=percentile(commit_ms, 0.95),
               read_p50=percentile(read_ms, 0.5),
               read_p99=percentile(read_ms, 0.99),
               late_p99=percentile(client["late_ms"], 0.99),
               blocks=len(inside), events=report["events"],
               learned_from=report["learned_from"],
               refused_why=sorted({w["refused"] for w in refused})[:5])
        if h.trace:
            # the steps of a height, as the 4-validator cell's readers
            # read them: here a note, because their entries in
            # BENCHMARK.json are pinned to that cell by a test
            r = SimpleNamespace(window=(t0, t1), passes=[])
            fired = program_spans.count(r, "cs:timeout")
            h.note("steps", timeouts=fired,
                   timeouts_per_100_heights=None if fired is None or
                   not inside else 100.0 * fired / len(inside),
                   newheight_p50_ms=program_spans.per_request_p50_ms(
                       r, "cs:NEW_HEIGHT"),
                   propose_p50_ms=program_spans.per_request_p50_ms(
                       r, "cs:PROPOSE"),
                   prevote_p50_ms=program_spans.per_request_p50_ms(
                       r, "cs:PREVOTE", "cs:PREVOTE_WAIT"),
                   precommit_p50_ms=program_spans.per_request_p50_ms(
                       r, "cs:PRECOMMIT", "cs:PRECOMMIT_WAIT"))

        # ---- the chain goes quiet: every mempool empty and two empty
        # blocks on every node since (`fleet`'s rule)
        with clock.phase("settle"):
            stop_watch.set()
            while True:
                clock.wait(lambda: not any(n.mempool.size() for n in nodes),
                           "the mempools to empty", 0.05)
                base = max(n.height for n in nodes)
                clock.wait(lambda: all(n.height >= base + 2 for n in nodes),
                           "two more blocks")
                if not any(n.mempool.size() for n in nodes) and not any(
                        store.load_block_meta(hh).header.num_txs
                        for hh in (base + 1, base + 2)):
                    break
            top = base + 1      # block top + 1 carries the hash after top

        with clock.phase("checks"):
            took = {"readback": time.perf_counter()}
            a_host, a_port = targets[0]
            b_host, b_port = targets[-1]
            # ---- read-backs first: the tree keeps its last versions
            # only, and an empty block a second goes by
            acked = [w for w in updates if w["commit_ms"] is not None]
            sample = rng.sample(acked, min(int(p["readback_sample"]),
                                           len(acked)))
            if acked:
                sample.append(max(acked,
                                  key=lambda w: (w["height"], w["index"])))
            values = ycsb.Values(h.seed, record_bytes)
            backs = []
            for w in sample:
                # written through one RPC node, read back from the other
                host, port = (b_host, b_port) if w["target"] == 0 \
                    else (a_host, a_port)
                backs.append((w, rpc_call(
                    host, port, "abci_query", path="/store",
                    data=w["key"].encode().hex(), height=0,
                    prove=True)["response"]))
            # the headers the read-backs are anchored at, and the audit's
            # range: it ends AUDIT_TAIL heights past the last anchor, and
            # the forged header is one of those, because the audit
            # certifies what comes before the header it rejects
            anchors = [resp["height"] + 1 for _w, resp in backs]
            last_anchor = max(anchors + [top + 1])
            audit_top = max(last_anchor + min(AUDIT_TAIL, n_audit // 2),
                            n_audit)

            # ---- the block log, replayed by the plain reference
            took["replay"] = time.perf_counter()
            ref = treeref.PlainTree(ycsb.records(h.seed, n_records,
                                                 record_bytes))
            # an empty block a second: the audit's last heights are there
            # by now
            clock.wait(lambda: min(n.height for n in nodes) >= audit_top,
                       f"every node at height {audit_top}")
            took["log"] = time.perf_counter()
            carried = {}                # height -> its header's app hash
            log, wrote = {}, {}         # (key, value) -> where; key -> when
            after = {}                  # height -> the replay's app hash
            rewrites = in_window = twice = 0
            for height in range(1, audit_top + 1):
                blk = rpc_call(a_host, a_port, "block",
                               height=height)["block"]
                carried[height] = bytes.fromhex(blk["header"]["app_hash"])
                if height > top:
                    continue
                txs = [bytes.fromhex(t) for t in blk["data"]["txs"]]
                keys_here = {}
                for i, tx in enumerate(txs):
                    k, _, v = tx.partition(b"=")
                    twice += (k, v) in log
                    log[(k, v)] = (height, i)
                    keys_here[k] = keys_here.get(k, 0) + 1
                    hist = wrote.setdefault(k, ([], []))
                    if hist[0] and hist[0][-1] == height:
                        hist[1][-1] = v
                    else:
                        hist[0].append(height)
                        hist[1].append(v)
                if h0 < height <= h1:
                    in_window += len(txs)
                    rewrites += sum(c for c in keys_here.values() if c > 1)
                after[height] = ref.apply_block(txs)
            # block h + 1 carries the app hash after block h
            app_bad = sum(1 for height in range(1, top + 1)
                          if after[height] != carried[height + 1])
            # a value is fresh to its update: one in the log twice is a
            # transaction the chain committed twice (the mempool of PR
            # 35's parent let gossip bring one back after its block)
            h.note("log", heights=top, txs=len(log) + twice,
                   committed_twice=twice)
            h.check("app_hashes_differing_from_plain_reference", app_bad, 0)
            client["same_block_rewrite_share"] = \
                100.0 * rewrites / in_window if in_window else None

            def value_at(key: bytes, item: int, version: int) -> bytes:
                """What the replay holds for `key` after block
                `version`."""
                heights, vals = wrote.get(key, ((), ()))
                at = bisect.bisect_right(heights, version)
                return vals[at - 1] if at else values.loaded(item)

            # ---- every read of the window
            took["reads"] = time.perf_counter()
            unproven = stale = 0
            proof_bytes = []
            for w in reads:
                resp = w["reply"]
                if resp is None:
                    continue            # refused or unanswered: `failed`
                proof_bytes.append(len(resp["proof"]) // 2)
                key = w["key"].encode()
                got = bytes.fromhex(resp["value"])
                version = resp["height"]
                try:
                    ok = treeref.verify(bytes.fromhex(resp["proof"]), key,
                                        got, carried[version + 1])
                except (treeref.Rejected, KeyError):
                    ok = False
                if not ok:
                    unproven += 1
                elif got != value_at(key, w["item"], version):
                    stale += 1
            # a proof's size is the tree's depth: a constant of the
            # deployment's recordcount, so a note and no metric
            h.note("reads", checked=len(proof_bytes),
                   proof_bytes_p50=percentile(proof_bytes, 0.5),
                   proof_bytes_max=max(proof_bytes, default=None))
            h.check("window_reads_whose_proof_does_not_verify", unproven, 0)
            h.check("window_reads_differing_from_plain_reference", stale, 0)
            h.check("window_updates_never_committed", sum(
                1 for w in updates if not w["refused"] and
                (w["key"].encode(), values.update(w["i"])) not in log), 0)

            # ---- the read-backs, and the two controls on each
            not_in_log = wrong = outside = early = 0
            rejected = {"flipped_sibling": 0, "wrong_value": 0}
            audit_heights = list(range(audit_top - n_audit + 1,
                                       audit_top + 1))
            for w, resp in backs:
                key = w["key"].encode()
                if log.get((key, values.update(w["i"])),
                           (None,))[0] != w["height"]:
                    not_in_log += 1
                got = bytes.fromhex(resp["value"])
                proof = bytes.fromhex(resp["proof"])
                anchor = resp["height"] + 1
                if not audit_heights[0] <= anchor <= last_anchor:
                    outside += 1
                if resp["height"] < w["height"]:
                    early += 1      # a version from before the update
                try:
                    ok = treeref.verify(proof, key, got, carried[anchor])
                except (treeref.Rejected, KeyError):
                    ok = False
                if not ok or got != value_at(key, w["item"],
                                             resp["height"]):
                    wrong += 1
                    continue
                if both_reject(flipped(proof), key, got, carried[anchor]):
                    rejected["flipped_sibling"] += 1
                if both_reject(proof, key, got + b"!", carried[anchor]):
                    rejected["wrong_value"] += 1
            h.note("read_backs", sampled=len(backs), anchors=[
                min(anchors, default=None), max(anchors, default=None)],
                audit=[audit_heights[0], audit_heights[-1]],
                controls_rejected=rejected)
            h.check("acknowledged_updates_missing_from_log", not_in_log, 0)
            h.check("read_backs_unproven_or_differing_from_plain_reference",
                    wrong, 0)
            h.check("read_backs_anchored_outside_what_the_audit_certifies",
                    outside, 0)
            h.check("read_backs_served_from_before_the_updates_height",
                    early, 0)
            h.check("proof_controls_not_rejected",
                    2 * (len(backs) - wrong) - sum(rejected.values()) +
                    (0 if all(rejected.values()) else 1), 0)
            ms = [n.block_store.load_block_meta(top) for n in nodes]
            h.check("nodes_disagreeing_at_last_height",
                    len({m.block_id.hash for m in ms}) +
                    len({m.header.app_hash for m in ms}) - 2, 0)

            # ---- the device: a lite audit of the committed chain, one
            # batch, with a forged header past the last anchor
            took["audit"] = time.perf_counter()
            forged_pos = rng.randrange(
                audit_heights.index(last_anchor) + 1, n_audit)
            valset, fcs = audit_chain(nodes, gen, audit_heights, forged_pos)
            n_sigs = sum(1 for f in fcs
                         for v in f.signed_header.commit.precommits
                         if v is not None)
            with probe.VerifierTap(verifier, h.spans, p.get("control")):
                with h.profile(), h.spans.span("lite_audit"):
                    try:
                        certify_chain(gen.chain_id, fcs, trusted=valset)
                        where = "certified"
                    except CertificationError as e:
                        where = str(e)
            took["end"] = time.perf_counter()
            at = list(took.values())
            h.note("lite_audit", heights=[audit_heights[0],
                                          audit_heights[-1]],
                   forged_height=audit_heights[forged_pos],
                   outcome=where[:120], signatures=n_sigs,
                   checks_s={k: round(b - a, 3) for k, a, b in
                             zip(took, at, at[1:])})
            h.check("audit_forged_header_not_rejected_at_its_height",
                    0 if where.startswith(
                        f"height {audit_heights[forged_pos]}:") else 1, 0)
            counters = probe.delta(probe.counters(verifier), c0)
            if not h.rehearsal:
                h.check("audit_signatures_off_device",
                        max(0, n_sigs - counters["verifier.jax_sigs"]), 0)
    finally:
        stop_watch.set()
        with clock.phase("stop"):
            child = state["child"]
            if child is not None and child.poll() is None:
                child.kill()
                child.wait(timeout=5.0)
            stoppers = [threading.Thread(target=node.stop,
                                         name=f"bench-stop-{i}")
                        for i, node in enumerate(nodes)]
            for t in stoppers:
                t.start()
            for t in stoppers:
                t.join(timeout=clock.left() + 5.0)
            shutil.rmtree(home, ignore_errors=True)
        clock.done()
        h.note("clock", **clock.report())
    client["window_s"] = h.seconds      # the client's window: by due time
    return Outcome(attempted=len(ops), failed=failed, counters=counters,
                   client=client)
