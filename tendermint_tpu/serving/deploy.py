"""Deployment driver (ISSUE 19 tentpole a).

The spawn/patch/supervise/teardown of a multi-process net as one
reusable object: materialize a ``Topology`` into per-node
homes, spawn one OS process per node, supervise them (a crash during
the run is RESTARTED with the same argv, up to ``max_restarts`` per
process — the edge tier's processes are cattle), optionally shape the
validator WAN with the chaos WireProxy (PR 13), and tear the net down
leak-clean (terminate -> wait -> kill, logs closed, tree removed).

The driver is deliberately transport-honest: nodes are real OS
processes over real TCP sockets, exactly what the open-loop harness
(serving/loadgen.py) must be pointed at for its numbers to mean
anything about a deployment.

A topology with `n_workers` > 0 spawns worker processes of several
validators each (serving/worker.py) and builds the validators of
`in_process` in the calling process (`local_nodes`: live Nodes, because
a chip belongs to one process). Workers are not restarted (their nodes
keep nothing on disk to come back from); `ask` puts one question to
every worker over its pipe. No child outlives the caller: every child
is the leader of a process group of its own, `stop` kills by group
after a bounded wait, and a worker leaves by itself when its pipe
reads end-of-file.
"""

from __future__ import annotations

import json
import os
import selectors
import shutil
import signal
import subprocess
import threading
import time
from typing import Dict, List, Optional

from tendermint_tpu import telemetry
from tendermint_tpu.serving.topology import (ProcSpec, Topology,
                                             draw_peer_graph, graph_links,
                                             materialize, seed_of)
from tendermint_tpu.utils.procs import free_port_block, node_child_env

_m_restarts = telemetry.counter(
    "deploy_restarts_total",
    "Deployment-driver process restarts after a crash, by node kind",
    ("kind",))
_m_procs = telemetry.gauge(
    "deploy_procs", "Processes currently supervised by the driver")


class Deployment:
    """Spawn, supervise and tear down one materialized topology.

    Lifecycle: ``start()`` -> (run / crash-restart under supervision)
    -> ``stop()``. ``clients()`` hands back one JSONRPCClient per
    process; ``wait(pred, ...)`` is the standard boot/progress gate.
    """

    def __init__(self, topo: Topology, out_dir: str,
                 child_env: Optional[dict] = None,
                 kind_env: Optional[Dict[str, dict]] = None,
                 max_restarts: int = 3):
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        if topo.base_port <= 0:
            topo.base_port = free_port_block(2 * topo.n_processes())
        self.topo = topo
        self.out_dir = out_dir
        self.specs: List[ProcSpec] = materialize(topo, out_dir)
        self.env = node_child_env(repo)
        self.env.update(topo.env)
        self.env.update(child_env or {})
        # per-kind env overlays, e.g. an admission envelope
        # (TM_TPU_RPC_RATE) on replica processes only
        self.kind_env = kind_env or {}
        self.max_restarts = max_restarts
        self.restarts: Dict[str, int] = {}
        self.dead: Dict[str, int] = {}       # name -> exit code, gave up
        self._procs: Dict[str, subprocess.Popen] = {}
        self._logs: Dict[str, object] = {}
        self._proxy = None
        self._stopping = False
        self._supervisor: Optional[threading.Thread] = None
        #: the validators of topo.in_process, live, once start() is back
        self.local_nodes: list = []

    # ------------------------------------------------------- lifecycle

    def start(self, wait: bool = True,
              ready_timeout_s: float = 60.0) -> "Deployment":
        """Spawn every process (and build the caller's own nodes). With
        workers, `wait` false returns before they have said they are
        ready: the caller has work to do meanwhile, and calls
        `await_ready` itself (no node dials before that). Spawn from a
        thread that lives as long as the net: a worker asks to die with
        the thread that started it (utils/procs.die_with_parent)."""
        if self.topo.wire and self.topo.kind == "validators":
            self._wire_up()
        for spec in self.specs:
            self._spawn(spec)
        _m_procs.set(len(self._procs))
        self._supervisor = threading.Thread(
            target=self._supervise, daemon=True, name="tm-deploy-sup")
        self._supervisor.start()
        if self.topo.n_workers > 0:
            try:
                self._start_local_nodes()
                if wait:
                    self.await_ready(ready_timeout_s)
            except BaseException:
                self.stop()
                raise
        return self

    def _start_local_nodes(self) -> None:
        """The validators the caller hosts, built as a worker builds
        its own (serving/worker.build_node), while the workers boot."""
        from tendermint_tpu.serving.worker import build_node
        serves = self.topo.rpc_validators
        for k in self.topo.in_process:
            self.local_nodes.append(build_node(
                os.path.join(self.out_dir, f"val{k}"), self.topo.in_memory,
                serves is None or k in serves))
        for node in self.local_nodes:
            node.start(dial=False)      # await_ready has them dial

    def await_ready(self, timeout_s: float) -> None:
        """Every node of every worker listens, or RuntimeError; then
        all of them, the caller's own too, are told to dial."""
        deadline = time.monotonic() + timeout_s
        got = self._read_lines(timeout_s)
        if len(got) == len(self._workers()):
            got = self.ask({"cmd": "dial"},
                           max(1.0, deadline - time.monotonic()))
        late = sorted(set(self._workers()) - set(got))
        if late:
            raise RuntimeError(
                f"workers not ready in {timeout_s} s: {late}\n" +
                "\n".join(f"--- {n} ---\n{self.log_tail(n)}"
                          for n in late))
        for node in self.local_nodes:
            node.dial_configured_peers()

    def _workers(self) -> Dict[str, subprocess.Popen]:
        return {s.name: self._procs[s.name] for s in self.specs
                if s.kind == "worker" and s.name in self._procs}

    def _read_lines(self, timeout_s: float) -> Dict[str, dict]:
        """One JSON line from every worker's pipe, or as many as come
        within `timeout_s`; a worker whose pipe closed is left out."""
        deadline = time.monotonic() + timeout_s
        sel = selectors.DefaultSelector()
        pending: Dict[str, bytearray] = {}
        for name, proc in self._workers().items():
            if proc.poll() is None:
                sel.register(proc.stdout, selectors.EVENT_READ, name)
                pending[name] = bytearray()
        got: Dict[str, dict] = {}
        try:
            while pending and time.monotonic() < deadline:
                for key, _ in sel.select(
                        max(0.0, min(0.5, deadline - time.monotonic()))):
                    name = key.data
                    chunk = os.read(key.fileobj.fileno(), 1 << 20)
                    pending[name] += chunk
                    if not chunk or b"\n" in chunk:
                        line, _, _rest = bytes(pending[name]).partition(
                            b"\n")
                        sel.unregister(key.fileobj)
                        del pending[name]
                        if line:
                            got[name] = json.loads(line)
        finally:
            sel.close()
        return got

    def ask(self, msg: dict, timeout_s: float = 5.0) -> Dict[str, dict]:
        """Put `msg` to every worker and gather one reply from each:
        {worker name: reply}, without the workers that did not answer
        within `timeout_s`."""
        line = (json.dumps(msg) + "\n").encode()
        for proc in self._workers().values():
            try:
                proc.stdin.write(line)
                proc.stdin.flush()
            except (OSError, ValueError):
                pass        # gone: its reply will be missing
        return self._read_lines(timeout_s)

    def _spawn(self, spec: ProcSpec) -> None:
        log = self._logs.get(spec.name)
        if log is None:
            log = open(os.path.join(spec.home, "node.log"), "a+")
            self._logs[spec.name] = log
        env = self.env
        if spec.kind in self.kind_env:
            env = dict(env)
            env.update(self.kind_env[spec.kind])
        # a worker talks over its pipes and logs its errors; every other
        # child logs both. A process group of its own, so that stop()
        # can kill whatever the child has started besides
        pipes = dict(stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                     stderr=log) if spec.kind == "worker" else \
            dict(stdout=log, stderr=subprocess.STDOUT)
        self._procs[spec.name] = subprocess.Popen(
            spec.argv, env=env, process_group=0, **pipes)

    def _wire_up(self) -> None:
        """Route every validator<->validator p2p link through the
        chaos WireProxy so the configured fault spec is the WAN shape
        BETWEEN processes; replicas keep dialing validators' real
        listeners (they model co-located edge boxes)."""
        from tendermint_tpu.chaos.wire import proxy_for_testnet
        from tendermint_tpu.p2p import NodeKey
        import json
        n = self.topo.n_validators
        self._proxy, _ = proxy_for_testnet(
            self.topo.wire, self.topo.wire_seed, n,
            p2p_port=lambda j: self.specs[j].p2p_port)
        for i in range(n):
            spec = self.specs[i]
            cfg_path = os.path.join(spec.home, "config", "config.json")
            cfg = json.load(open(cfg_path))
            keys = [NodeKey.load_or_generate(os.path.join(
                self.specs[j].home, "config", "node_key.json"))
                for j in range(n)]
            cfg["p2p"]["persistent_peers"] = ",".join(
                f"{keys[j].id()}@127.0.0.1:{self._proxy.ports[(i, j)]}"
                for j in range(n) if j != i)
            # PEX would learn the direct addresses and route around
            # the proxy
            cfg["p2p"]["pex"] = False
            json.dump(cfg, open(cfg_path, "w"))
        self._proxy.start()

    def _supervise(self) -> None:
        """Crash/restart loop: a process that exits while the
        deployment is live is respawned with its own argv (bounded per
        process); exhausted processes are recorded in ``dead``."""
        by_name = {s.name: s for s in self.specs}
        while not self._stopping:
            for name, proc in list(self._procs.items()):
                rc = proc.poll()
                if rc is None or self._stopping:
                    continue
                if name in self.dead:
                    continue
                n = self.restarts.get(name, 0)
                if n >= self.max_restarts or \
                        by_name[name].kind == "worker":
                    self.dead[name] = rc
                    continue
                self.restarts[name] = n + 1
                _m_restarts.labels(by_name[name].kind).inc()
                self._spawn(by_name[name])
            _m_procs.set(sum(1 for p in self._procs.values()
                             if p.poll() is None))
            time.sleep(0.5)

    def stop(self, cleanup: bool = True, grace_s: float = 10.0) -> None:
        """Workers are told to stop over their pipes, every other child
        gets SIGTERM; whatever is alive `grace_s` later is killed with
        its process group. The caller's own nodes stop meanwhile."""
        self._stopping = True
        if self._supervisor is not None:
            self._supervisor.join(timeout=5)
        workers = self._workers()
        for name, proc in self._procs.items():
            if proc.poll() is not None:
                continue
            if name in workers:
                try:
                    proc.stdin.write(b'{"cmd": "stop"}\n')
                    proc.stdin.close()     # and end-of-file says it again
                except (OSError, ValueError):
                    pass
            else:
                proc.terminate()
        stoppers = [threading.Thread(target=node.stop, daemon=True,
                                     name=f"tm-deploy-stop-{i}")
                    for i, node in enumerate(self.local_nodes)]
        for t in stoppers:
            t.start()
        deadline = time.monotonic() + grace_s
        for proc in self._procs.values():
            try:
                proc.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        self.kill_all()
        for proc in self._procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
            for pipe in (proc.stdin, proc.stdout):
                if pipe is not None:
                    try:
                        pipe.close()
                    except OSError:
                        pass
        for t in stoppers:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
        self.local_nodes = []
        if self._proxy is not None:
            self._proxy.stop()
            self._proxy = None
        for log in self._logs.values():
            log.close()
        self._logs.clear()
        _m_procs.set(0)
        if cleanup:
            shutil.rmtree(self.out_dir, ignore_errors=True)

    def kill_all(self) -> None:
        """SIGKILL, now, to every child that is still there, and to its
        whole process group, so that whatever the child started goes
        with it (a child's pid is its group's, and cannot be another's
        while the child is not reaped). No wait: `stop` does that."""
        for proc in list(self._procs.values()):
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    # --------------------------------------------------------- access

    def declared_links(self) -> List[tuple]:
        """The undirected validator links the topology declares, (a, b)
        with a < b: the seeded draw's where `dial_k` is set, else the
        full mesh."""
        n = self.topo.n_validators
        if self.topo.dial_k > 0:
            return graph_links(draw_peer_graph(n, self.topo.dial_k,
                                               seed_of(self.topo)))
        return [(a, b) for a in range(n) for b in range(a + 1, n)]

    def spec(self, name: str) -> ProcSpec:
        for s in self.specs:
            if s.name == name:
                return s
        raise KeyError(name)

    def alive(self, name: str) -> bool:
        p = self._procs.get(name)
        return p is not None and p.poll() is None

    def kill(self, name: str) -> None:
        """Hard-kill one process (the supervisor will restart it)."""
        self._procs[name].kill()

    def clients(self, kind: Optional[str] = None) -> list:
        from tendermint_tpu.rpc.client import JSONRPCClient
        return [JSONRPCClient(s.rpc_address) for s in self.specs
                if kind is None or s.kind == kind]

    def log_tail(self, name: str, n: int = 1500) -> str:
        log = self._logs.get(name)
        if log is None:
            return ""
        log.flush()
        log.seek(0)
        return log.read()[-n:]

    # ---------------------------------------------------------- waits

    def wait(self, pred, timeout_s: float, what: str,
             kind: Optional[str] = None) -> None:
        """Wait until pred(client) holds for every process of `kind`
        (all when None). Raises with log tails on timeout or when a
        process dies past its restart budget."""
        from tendermint_tpu.rpc.client import RPCClientError
        clients = self.clients(kind)
        names = [s.name for s in self.specs
                 if kind is None or s.kind == kind]
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.dead:
                break
            try:
                if all(pred(c) for c in clients):
                    return
            except (OSError, ConnectionError, RPCClientError, KeyError):
                pass    # not up yet / route not registered yet
            time.sleep(0.5)
        tails = "\n".join(f"--- {n} ---\n{self.log_tail(n)}"
                          for n in names)
        raise RuntimeError(
            f"{what}: dead={self.dead} restarts={self.restarts}\n{tails}")

    def wait_height(self, h: int, timeout_s: float = 120.0,
                    kind: str = "validator") -> None:
        self.wait(lambda c: c.call("status")["latest_block_height"] >= h,
                  timeout_s, f"no progress to height {h}", kind=kind)


def run_shardset(args) -> int:
    """`cli shardset`: one process assembling N chains behind one
    front door (shard/set.py) — the sharded front-door process of a
    shard-set topology. Chains run the test consensus profile (this
    is a serving-plane process, not a WAN replica) with on-disk homes
    under --home when given."""
    from tendermint_tpu.node import _parse_laddr
    from tendermint_tpu.shard.set import ShardSet

    ss = ShardSet(n_shards=args.shards, home=(args.home or None))
    ss.start()
    host, port = ss.serve(*_parse_laddr(args.laddr))
    print(f"shardset front door on {host}:{port} "
          f"(chains: {','.join(ss.chains)})", flush=True)
    deadline = (time.time() + args.max_seconds
                if args.max_seconds else None)
    last = -1
    try:
        while deadline is None or time.time() < deadline:
            time.sleep(0.5)
            f = ss.frontier()
            if f != last:
                last = f
                print(f"frontier height={f}", flush=True)
    except KeyboardInterrupt:
        pass
    ss.stop()
    print(f"shardset stopped at frontier {last}")
    return 0
