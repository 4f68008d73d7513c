"""Driver `fleet_procs`: clients of a served net of a hundred validators.

The net is the program's own multi-process assembly
(tendermint_tpu/serving/topology.py and deploy.py): this file holds no
genesis, key or peer wiring. It turns the configuration file into a
`Topology` (stake by the configuration's law, rank dealt to validator
index by a seeded shuffle; K dialled peers of a seeded draw; delay by
region; several validators to a worker process that never imports JAX;
the two RPC validators in THIS process, because a chip belongs to one
process) and lets `Deployment` build it. The load is
benchmark/loadgen.py's child, as in the `fleet` driver, and the cell's
traffic is that cell's, so that the two cells differ in the deployment
alone.

**The run's clock.** A run is stopped from outside 360 s after its
process started, so it keeps a clock of its own: every phase has a
budget (the traffic file's `budget_s`), every wait takes its timeout
from what is left of its phase's limit (twice its budget, or what the
traffic file says for the drain), and one absolute deadline, `t_start +
deadline_s`, is armed before anything is built and handed to the
workers as their `max_seconds`. Past it the run kills its children,
says on standard error which phase it was in and what each phase took,
and exits 5 by itself. The phases' seconds go out as a `{"bench":
"clock"}` line before the result.

**Compiles.** The device runs one thing here: the light-client audit of
the committed chain after the window, one `certify_chain` over
`audit_heights` commits, one batch, one program. Set-up first-calls
that program on a toy chain of the same shape before the net boots
(beside a hundred booting nodes the compile took twice as long), and
compiles nothing else; after the run the driver checks that every
program set-up compiled was called again and that nothing compiled
later.

`correct`, outside the window, every limit 0: the `fleet` driver's
checks (writes in the log, acknowledged writes read back from the other
RPC node, app hashes against kvref.PlainKV, all nodes, now all hundred,
agreeing at the last common height, the device audit with its forged
header rejected at its height and no audit signature off the device);
every committed height's precommits weigh more than 2/3 of the stake
by benchmark/stakeref.py and commitref.py (OpenSSL); every height's
proposer is stakeref's; two controls that must read above 0 (a commit
cut to its smallest signers is refused, one cut to its largest
accepted, by the program's `verify_commit` and by the reference alike);
no peer banned; every declared link held at the end; the two checks of
the compiles.
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
from contextlib import contextmanager
from typing import Dict, List, Optional

from benchmark import commitref, probe, stakeref
from benchmark.chain import chain_id_of
from benchmark.drivers.fleet import audit_chain
from benchmark.harness import Outcome
from benchmark.kvref import PlainKV
from benchmark.loadgen import rpc_call
from benchmark.stats import percentile, quartile_spread

PHASES = ("start", "compile", "boot", "links", "warm", "window", "drain",
          "settle", "checks", "stop")
OVERDUE_EXIT = 5


class Overdue(TimeoutError):
    """A wait outlasted what was left of its phase."""


class RunClock:
    """The run's own clock: phases in order, each with a budget and a
    limit, under one absolute deadline."""

    def __init__(self, t_start: float, deadline_s: float,
                 budget_s: Dict[str, float],
                 limit_s: Optional[Dict[str, float]] = None,
                 on_deadline=None, exit_fn=os._exit):
        self.t_start = t_start                      # time.time()
        self.deadline = t_start + deadline_s
        self.budget = {p: float(budget_s.get(p, 0.0)) for p in PHASES}
        self.limit = {p: 2.0 * b for p, b in self.budget.items()}
        self.limit.update(limit_s or {})
        self.seconds: Dict[str, float] = {
            "start": max(0.0, time.time() - t_start)}
        self.phase_name = "start"
        self._phase_t0 = time.time()
        self._on_deadline, self._exit = on_deadline, exit_fn
        self._done = threading.Event()
        self._watch = threading.Thread(target=self._watchdog, daemon=True,
                                       name="bench-deadline")
        self._watch.start()

    @contextmanager
    def phase(self, name: str):
        self.phase_name, self._phase_t0 = name, time.time()
        try:
            yield self
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + \
                time.time() - self._phase_t0

    def left(self) -> float:
        """Seconds a wait that starts now may take: what is left of the
        phase's limit, and never past the deadline."""
        now = time.time()
        return max(0.0, min(
            self._phase_t0 + self.limit[self.phase_name] - now,
            self.deadline - now))

    def wait(self, cond, what: str, poll_s: float = 0.02) -> None:
        until = time.monotonic() + self.left()
        while not cond():
            if time.monotonic() > until:
                raise Overdue(
                    f"phase {self.phase_name!r}: timed out waiting for "
                    f"{what} ({self.report()})")
            time.sleep(poll_s)

    def report(self) -> dict:
        return {"phase": self.phase_name,
                "took_s": round(time.time() - self.t_start, 3),
                "seconds": {p: round(s, 3)
                            for p, s in self.seconds.items()},
                "budget_s": self.budget}

    def done(self) -> None:
        self._done.set()

    def _watchdog(self) -> None:
        if self._done.wait(max(0.0, self.deadline - time.time())):
            return
        # in the phase's own seconds, what it has had so far
        self.seconds[self.phase_name] = self.seconds.get(
            self.phase_name, 0.0) + time.time() - self._phase_t0
        print(f"benchmark: the run's own deadline of "
              f"{self.deadline - self.t_start:.0f} s passed in phase "
              f"{self.phase_name!r}: {json.dumps(self.report())}",
              file=sys.stderr, flush=True)
        try:
            if self._on_deadline is not None:
                self._on_deadline()
        finally:
            self._exit(OVERDUE_EXIT)


def usable_cores() -> int:
    """The cores this process may run on: what the workers share."""
    return len(os.sched_getaffinity(0))


def stake_law(n: int, scale: int) -> List[int]:
    """The configuration's stake by rank r = 1..n."""
    return [scale // (r + 2) for r in range(1, n + 1)]


def topology_of(h, clock: RunClock):
    """The configuration file as a `Topology`, and the rank each
    validator index was dealt."""
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.serving import Topology
    p = h.params
    if int(p["max_num_peers"]) != P2PConfig().max_num_peers or not p["pex"]:
        raise ValueError("the deployment states the program's own defaults "
                         "for max_num_peers and pex; they have moved")
    n = int(p["validators"])
    rng = random.Random(f"{h.seed}/net100/ranks")
    ranks = list(range(1, n + 1))
    rng.shuffle(ranks)                      # ranks[i]: validator i's
    law = stake_law(n, int(p["stake_scale"]))
    powers = [law[r - 1] for r in ranks]
    # the RPC validators: the two ranks next to the median
    mid = n // 2
    in_process = tuple(sorted(ranks.index(r) for r in (mid, mid + 1)))
    workers = max(1, min(int(p["workers_max"]), usable_cores(), n - 2))
    return Topology(
        kind="validators", n_validators=n,
        chain_id=chain_id_of("net100", h.seed), fast_timeouts=False,
        timeouts=dict(p["consensus"]), powers=powers, key_seed=h.seed,
        dial_k=int(p["dialled_peers"]),
        addr_book_strict=bool(p["addr_book_strict"]),
        n_workers=workers, in_process=in_process,
        rpc_validators=in_process, in_memory=p["stores"] == "in-memory",
        regions=int(p["regions"]), region_delay_ms=p["region_delay_ms"],
        region_jitter_ms=float(p["region_jitter_ms"]),
        verifier_backend=p["verifier_backend"], telemetry=False,
        log_level="error",
        # past the run's own deadline no worker is left, whatever
        # became of this process
        max_seconds=max(1.0, clock.deadline - time.time())), ranks


class ProposalWatch:
    """Who proposed what the in-process nodes completed: at every
    `CompleteProposal` of a node, on its consensus thread, the height,
    the round, the proposer its state machine holds for that round, and
    the proposal's sign-bytes and signature."""

    def __init__(self, nodes):
        self.seen: Dict[tuple, tuple] = {}
        self._subs = []
        for i, node in enumerate(nodes):
            sub = node.event_bus.subscribe(
                f"bench-proposals-{i}", "tm.event = 'CompleteProposal'")
            sub.on_put = lambda node=node: self._note(node)
            self._subs.append((node, f"bench-proposals-{i}", sub))

    def _note(self, node) -> None:
        rs = node.consensus.rs
        prop = rs.proposal
        if prop is None:
            return
        self.seen.setdefault((rs.height, rs.round), (
            rs.validators.proposer().address,
            prop.sign_bytes(node.gen_doc.chain_id), prop.signature))

    def close(self) -> int:
        dropped = 0
        for node, name, sub in self._subs:
            dropped += sub.dropped
            node.event_bus.unsubscribe_all(name)
        return dropped


def plain_bid(block_id) -> tuple:
    """A program BlockID as commitref's plain triple."""
    return (block_id.hash, block_id.parts.total, block_id.parts.hash)


def plain_commit(commit):
    """A program Commit as commitref's plain data."""
    return [None if v is None else commitref.PlainVote(
        v.height, v.round, v.type, v.timestamp_ns, plain_bid(v.block_id),
        v.signature) for v in commit.precommits]


def cut_commit(commit, keep: set):
    """The commit with only the precommits of the validator indices in
    `keep`."""
    from tendermint_tpu.types.block import Commit
    return Commit(commit.block_id, [
        v if v is not None and v.validator_index in keep else None
        for v in commit.precommits])


def program_counters() -> dict:
    """What the new readers read, as it stands now (each reader's own
    `now`)."""
    from benchmark.metrics import (n100_link_delay_p50_ms,
                                   n100_p2p_msgs_per_height,
                                   n100_votes_duplicate_share)
    return {"votes": n100_votes_duplicate_share.now(),
            "msgs_recv": n100_p2p_msgs_per_height.now(),
            "link_delay": n100_link_delay_p50_ms.now()}


def programs_called(first_calls_at_setup: dict, kernels_at_setup: dict,
                    kernels_now: dict) -> List[str]:
    """Of the programs whose first call fell in set-up
    ("kernel[rows]"), those whose kernel has not been dispatched
    since."""
    return sorted(shape for shape in first_calls_at_setup
                  if kernels_now.get(shape.split("[")[0], 0) <=
                  kernels_at_setup.get(shape.split("[")[0], 0))


def run(h) -> Outcome:
    from tendermint_tpu import telemetry
    from tendermint_tpu.lite.certifier import (CertificationError,
                                               certify_chain)
    from tendermint_tpu.models.verifier import default_verifier
    from tendermint_tpu.ops import ed25519
    from tendermint_tpu.serving import Deployment
    from tendermint_tpu.serving.worker import node_report
    from tendermint_tpu.telemetry import slo
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    p = h.params
    rate, drain_s = float(p["rate"]), float(p["drain_s"])
    drain_max_s = float(p.get("drain_max_s", drain_s))
    n_audit = int(p["audit_heights"])
    home = tempfile.mkdtemp(prefix="bench-net100-")
    state = {"deployment": None, "child": None}

    def kill_children() -> None:
        """What the deadline does: no wait, no grace."""
        child, d = state["child"], state["deployment"]
        if child is not None and child.poll() is None:
            child.kill()
        if d is not None:
            d.kill_all()
        shutil.rmtree(home, ignore_errors=True)

    clock = RunClock(h.t_start, float(p["deadline_s"]), p["budget_s"],
                     # the drain is the load generator's to end; the
                     # chain goes quiet a block or two after it, and a
                     # second round there costs 4-6 s
                     limit_s={"drain": drain_max_s + 5.0,
                              "window": h.seconds + 15.0, "settle": 30.0},
                     on_deadline=kill_children)
    rng = random.Random(f"{h.seed}/fleet")
    verifier = default_verifier()
    c0 = probe.counters(verifier)
    report_path = os.path.join(home, "loadgen.json")
    nodes: list = []
    seen = []       # (height, perf_counter when node 0 first showed it)
    stop_watch = threading.Event()
    proposals = None

    def watch_heights():
        last = 0
        while not stop_watch.is_set():
            now_h = nodes[0].height
            if now_h > last:
                seen.append((now_h, time.perf_counter()))
                last = now_h
            time.sleep(0.01)

    try:
        # ---- set-up. The net's shape before anything else: a program
        # that cannot build it (the parent commit under this
        # benchmark's files) fails here, at once, not after a compile
        topo, ranks = topology_of(h, clock)
        # then the audit's one program, then the net. In this order: a
        # hundred booting nodes take every core, and the compile beside
        # them took twice its time alone (PERF.md section 6, PR 31)
        with clock.phase("compile"), h.spans.span("warm_audit"):
            if not h.rehearsal:
                # first called here on a toy chain of the audit's shape
                from benchmark.chain import LiteChain
                toy = LiteChain(h.seed, n_audit, int(p["validators"]),
                                sign="openssl")
                toy_valset, toy_fcs = toy.decode()
                certify_chain(toy.chain_id, toy_fcs, trusted=toy_valset)
                del toy, toy_valset, toy_fcs
            setup_stats = ed25519.predecomp_stats()
        with clock.phase("boot"), h.spans.span("boot_net"):
            d = state["deployment"] = Deployment(
                topo, os.path.join(home, "net"), max_restarts=0)
            links = d.declared_links()
            # from this thread, which outlives the net: a worker dies
            # with the thread that started it
            d.start(wait=False)
        with clock.phase("links"), h.spans.span("links"):
            d.await_ready(clock.left())
            nodes = list(d.local_nodes)
            # the in-process validators are traced in a traced run, the
            # workers never (their configuration says telemetry off)
            telemetry.configure(enabled=h.trace)
            if h.trace:
                slo.configure(mode="on",
                              sample=float(p.get("slo_sample", 0.25)))
            gen = nodes[0].gen_doc
            valset = ValidatorSet([Validator(v.pubkey, v.power)
                                   for v in gen.validators])
            ref_vals = [(v.address, v.pubkey, v.voting_power)
                        for v in valset.validators]
            ref_keys = [(pk, pw) for _a, pk, pw in ref_vals]
            id_of: Dict[int, str] = {}

            def fleet_report(last: int = 8) -> Dict[str, dict]:
                """{validator name: its report}, all hundred: the
                workers' over their pipes, the in-process two read
                here."""
                out = {f"val{k}": node_report(node, last)
                       for k, node in zip(topo.in_process, nodes)}
                replies = d.ask({"cmd": "report", "last": last},
                                min(5.0, clock.left()))
                # the workers' CPU seconds, where every one answered
                out["cpu_s"] = sum(r["cpu_s"] for r in replies.values()) \
                    if len(replies) == topo.n_workers else None
                for r in replies.values():
                    out.update(r["nodes"])
                return out

            def missing_links(rep) -> List[tuple]:
                for name, doc in rep.items():
                    if name != "cpu_s":
                        id_of[int(name[3:])] = doc["id"]
                return [(a, b) for a, b in links
                        if f"val{a}" not in rep or f"val{b}" not in rep or
                        id_of[b] not in rep[f"val{a}"]["peers"] or
                        id_of[a] not in rep[f"val{b}"]["peers"]]

            def all_links_held():
                rep = fleet_report(1)
                return len(rep) == topo.n_validators + 1 and \
                    not missing_links(rep)
            clock.wait(all_links_held, "every declared link", 0.5)
            clock.wait(lambda: all(n.height >= 1 for n in nodes),
                       "the first block")
            degree = sorted(
                sum(1 for a, b in links if k in (a, b))
                for k in range(topo.n_validators))
            h.note("net", validators=topo.n_validators,
                   workers=topo.n_workers,
                   nodes_per_worker=[len(w)
                                     for w in topo.hosted_by_worker()],
                   in_process=list(topo.in_process),
                   in_process_ranks=[ranks[k] for k in topo.in_process],
                   declared_links=len(links),
                   declared_degree=[degree[0],
                                    sum(degree) / len(degree), degree[-1]],
                   region_stake=[
                       sum(pw for k, pw in enumerate(topo.powers)
                           if topo.region_of(k) == r) / sum(topo.powers)
                       for r in range(topo.regions)],
                   verifier_backend=p["verifier_backend"])

        with clock.phase("warm"), h.spans.span("warm_under_load"):
            proposals = ProposalWatch(nodes)
            targets = [list(n.rpc_address) for n in nodes]
            # the launcher makes the child one that cannot outlive this
            # process (tendermint_tpu/utils/procs.py)
            child = state["child"] = subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.utils.procs",
                 sys.executable, "-m", "benchmark.loadgen", json.dumps({
                     "targets": targets, "rate": rate, "seed": h.seed,
                     "tx_bytes": int(p["tx_bytes"]),
                     "keyspace": int(p["keyspace"]),
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
            cpu_open = fleet_report(1)["cpu_s"]

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
            n100_open = program_counters() if h.trace else None
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
            cpu_close = fleet_report(1)["cpu_s"]
            n100_close = program_counters() if h.trace else None

        with clock.phase("drain"):
            try:
                child.wait(timeout=clock.left())
            except subprocess.TimeoutExpired:
                # whatever is unanswered now counts as failed
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
        writes = report["window"]
        refused = [w for w in writes if w["refused"]]
        failed = len(refused) + sum(
            1 for w in writes if not w["refused"] and w["commit_ms"] is None)
        commit_ms = [w["commit_ms"] for w in writes
                     if w["commit_ms"] is not None]
        cores = usable_cores()
        client = {
            "commit_ms": commit_ms,
            "check_ms": [w["check_ms"] for w in writes
                         if w["check_ms"] is not None],
            "late_ms": [w["late_ms"] for w in writes],
            "offered": report["offered"], "events": report["events"],
            "learned_from": report["learned_from"],
            "refused": len(refused), "acked": len(commit_ms),
            "backlog_open": backlog0, "backlog_close": backlog1,
            "in_process": len(nodes),
            "workers_cpu_share": None if None in (cpu_open, cpu_close)
            else 100.0 * (cpu_close - cpu_open) / (cores * (t1 - t0)),
        }
        if h.trace:
            client["n100_open"], client["n100_close"] = n100_open, n100_close
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
               workers_cpu_share=client["workers_cpu_share"],
               refused_why=sorted({w["refused"] for w in refused})[:5])

        if h.trace:
            # the steps of a height and the timeouts that moved a state
            # machine, as the 4-validator cell's readers read them: here
            # a note, because their entries in BENCHMARK.json are pinned
            # to that cell by a test (ROADMAP Queue 3 item 13)
            from types import SimpleNamespace
            from benchmark import program_spans
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

        # ---- the chain goes quiet: the load has stopped, so once both
        # RPC nodes' mempools are empty every write they ever admitted
        # is in a block, and the state after the higher node's last
        # block is the one the reads below are held to
        with clock.phase("settle"):
            stop_watch.set()
            clock.wait(lambda: not any(n.mempool.size() for n in nodes),
                       "both mempools to empty", 0.05)
            top = max(n.height for n in nodes)
            clock.wait(lambda: min(n.height for n in nodes) >= top and
                       min(n.height for n in nodes) >= n_audit,
                       f"both RPC nodes at height {top} and a chain of "
                       f"{n_audit} blocks", 0.05)
            top = max(top, n_audit)
            after_top = {n.consensus.state.app_hash for n in nodes
                         if n.height == top}

        with clock.phase("checks"):
            took = {"replay": time.perf_counter()}
            a_host, a_port = targets[0]
            b_host, b_port = targets[-1]
            ref, log, final = PlainKV(), {}, {}
            app_bad = 0
            for height in range(1, top + 1):
                blk = rpc_call(a_host, a_port, "block",
                               height=height)["block"]
                txs = [bytes.fromhex(t) for t in blk["data"]["txs"]]
                for i, tx in enumerate(txs):
                    k, _, v = tx.partition(b"=")
                    final[k] = v
                    log[(k, v)] = (height, i)
                after = ref.apply_block(txs)
                # block height + 1 carries the app hash after height;
                # after the last, the nodes' own state does
                carried = {store.load_block_meta(height + 1).header.app_hash} \
                    if height < top else after_top
                if ref.store and carried - {after}:
                    app_bad += 1
            h.check("app_hashes_differing_from_plain_reference", app_bad, 0)
            h.check("window_writes_never_committed", sum(
                1 for w in writes if not w["refused"] and
                (w["key"].encode(), w["value"].encode("latin-1"))
                not in log), 0)
            acked = [w for w in writes if w["commit_ms"] is not None]
            sample = rng.sample(acked, min(int(p["readback_sample"]),
                                           len(acked)))
            if acked:
                sample.append(max(acked,
                                  key=lambda w: (w["height"], w["index"])))
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
                if bytes.fromhex(got["response"]["value"] or "") != \
                        final.get(k):
                    wrong += 1
            h.check("acknowledged_writes_missing_from_log", not_in_log, 0)
            h.check("read_backs_differing_from_plain_reference", wrong, 0)

            took["reports"] = time.perf_counter()
            # all hundred, at the last height all of them have
            rep = fleet_report(8)
            docs = {k: v for k, v in rep.items() if k != "cpu_s"}
            common = min(doc["height"] for doc in docs.values())
            at_common = [doc["hashes"].get(str(common))
                         for doc in docs.values()]
            h.note("fleet", reports=len(docs), common_height=common,
                   heights=[min(d_["height"] for d_ in docs.values()),
                            max(d_["height"] for d_ in docs.values())])
            h.check("nodes_disagreeing_at_last_height",
                    (topo.n_validators - len(docs)) +
                    sum(1 for x in at_common if x != at_common[0]) +
                    (0 if at_common[0] else 1), 0)
            banned = {k: v["banned"] for k, v in docs.items() if v["banned"]}
            gone = missing_links(rep)
            degree_now = sorted(len(v["peers"]) for v in docs.values())
            h.note("peers", banned=banned, missing_links=gone[:8],
                   degree=[degree_now[0],
                           sum(degree_now) / len(degree_now),
                           degree_now[-1]])
            h.check("peers_banned_in_run", len(banned), 0)
            h.check("declared_links_not_held", len(gone), 0)

            # ---- stake: every commit's weight, every height's proposer
            took["stake"] = time.perf_counter()
            short = 0
            rounds = []
            for height in range(1, top + 1):
                meta = store.load_block_meta(height)
                commit = store.load_seen_commit(height)
                rounds.append(commit.round())
                why = commitref.verify_commit(
                    gen.chain_id, ref_keys, plain_bid(meta.block_id),
                    height, plain_commit(commit))
                signers = [valset.validators[v.validator_index].address
                           for v in commit.precommits
                           if v is not None and v.block_id == meta.block_id]
                if why is not None or not stakeref.tally(ref_vals,
                                                         signers)[2]:
                    short += 1
            h.check("commits_short_of_two_thirds_stake", short, 0)
            want = stakeref.proposers(ref_vals, rounds)
            by_addr = {a: pk for a, pk, _pw in ref_vals}
            from benchmark.kvref import openssl_verify
            differing = observed = 0
            for (height, round_), (addr, sb, sig) in sorted(
                    proposals.seen.items()):
                if height > top or round_ != rounds[height - 1]:
                    continue        # a round that did not commit
                observed += 1
                if addr != want[height - 1] or not openssl_verify(
                        by_addr[want[height - 1]], sb, sig):
                    differing += 1
            dropped = proposals.close()
            proposals = None
            h.note("proposers", observed=observed, heights=top,
                   dropped=dropped, rounds_gt0=sum(1 for r in rounds if r),
                   distinct=len(set(want)))
            h.check("proposers_differing_from_plain_reference", differing +
                    (0 if observed >= len(client["blocks"]) else 1), 0)

            # ---- two controls on one real commit: cut to its smallest
            # signers it is refused, cut to its largest it is accepted,
            # by the program and by the reference alike
            meta, commit = store.load_block_meta(top), \
                store.load_seen_commit(top)
            signed = {v.validator_index for v in commit.precommits
                      if v is not None and v.block_id == meta.block_id}
            idx_of = {v.address: i for i, v in enumerate(valset.validators)}
            small = {idx_of[a] for a in stakeref.smallest(
                ref_vals, int(p["control_smallest"]))} & signed
            k_large = int(p["control_largest"])
            while True:
                large = {idx_of[a] for a in
                         stakeref.largest(ref_vals, k_large)} & signed
                if stakeref.tally(ref_vals, [
                        valset.validators[i].address for i in large])[2] \
                        or k_large >= len(ref_vals):
                    break
                k_large += 1    # a large validator's precommit came late
            wrongly = 0
            refused_small = accepted_large = 0
            for keep, expect_ok in ((small, False), (large, True)):
                cut = cut_commit(commit, keep)
                try:
                    valset.verify_commit(gen.chain_id, meta.block_id, top,
                                         cut)
                    prog_ok = True
                except ValueError:
                    prog_ok = False
                ref_ok = commitref.verify_commit(
                    gen.chain_id, ref_keys, plain_bid(meta.block_id), top,
                    plain_commit(cut)) is None
                if prog_ok != expect_ok or ref_ok != expect_ok:
                    wrongly += 1
                elif expect_ok:
                    accepted_large += 1
                else:
                    refused_small += 1
            total = sum(pw for _a, _pk, pw in ref_vals)
            h.note("stake_controls", height=top, signers=len(signed),
                   smallest=len(small), largest=len(large),
                   largest_of=k_large,
                   smallest_stake_share=sum(
                       valset.validators[i].voting_power
                       for i in small) / total,
                   largest_stake_share=sum(
                       valset.validators[i].voting_power
                       for i in large) / total)
            h.check("stake_controls_answered_wrongly", wrongly +
                    (0 if refused_small and accepted_large else 1), 0)

            # ---- the device: a lite audit of the committed chain
            took["audit"] = time.perf_counter()
            heights = list(range(top - n_audit + 1, top + 1))
            forged_pos = rng.randrange(n_audit // 2, n_audit)
            _valset, fcs = audit_chain(nodes, gen, heights, forged_pos)
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
            h.note("lite_audit", heights=[heights[0], heights[-1]],
                   forged_height=heights[forged_pos], outcome=where[:120],
                   signatures=n_sigs, checks_s={
                       k: round(b - a, 3) for k, a, b in
                       zip(took, at, at[1:])})
            h.check("audit_forged_header_not_rejected_at_its_height",
                    0 if where.startswith(f"height {heights[forged_pos]}:")
                    else 1, 0)
            counters = probe.delta(probe.counters(verifier), c0)
            # what the two in-process validators verified on the host
            # is the process's work too
            for n in nodes:
                counters["verifier.sigs"] += n.verifier.stats["sigs"]
            if not h.rehearsal:
                h.check("audit_signatures_off_device",
                        max(0, n_sigs - counters["verifier.jax_sigs"]), 0)
                # ---- the compiles: set-up's programs were all called
                # again, and nothing compiled after set-up
                end_stats = ed25519.predecomp_stats()
                idle = programs_called(
                    setup_stats["first_call_s"], setup_stats, end_stats)
                late = sorted(
                    set(end_stats["first_call_s"]) -
                    set(setup_stats["first_call_s"])) + sorted(
                    {name for at, kind, name, _s in h.compiles.events
                     if kind == "compile" and at > t0})
                h.note("programs", first_call_s=end_stats["first_call_s"],
                       never_called_again=idle, compiled_after_setup=late,
                       setup_compiled=sorted(
                           {name for at, kind, name, _s in h.compiles.events
                            if kind == "compile" and at <= t0}))
                h.check("programs_compiled_in_setup_never_called",
                        len(idle), 0)
                h.check("programs_compiled_after_setup", len(late), 0)
    finally:
        stop_watch.set()
        with clock.phase("stop"):
            if proposals is not None:
                proposals.close()
            child = state["child"]
            if child is not None and child.poll() is None:
                child.kill()
                child.wait(timeout=5.0)
            if state["deployment"] is not None:
                state["deployment"].stop(grace_s=5.0)
            shutil.rmtree(home, ignore_errors=True)
        clock.done()
        h.note("clock", **clock.report())
    client["window_s"] = h.seconds      # the client's window: by due time
    return Outcome(attempted=len(writes), failed=failed, counters=counters,
                   client=client)
