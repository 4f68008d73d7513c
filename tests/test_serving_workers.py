"""What a net of many validators on one host asks of the program's
multi-process assembler (serving/topology.py, deploy.py, worker.py) and
of the link seam under it (p2p/fuzz.py's delay mode): stake in the
genesis, a sparse connected peer graph from a seed, several validators
to a worker process with some hosted by the caller, delay by region
pair, and children that cannot outlive their parent."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from tendermint_tpu.serving import Deployment, Topology
from tendermint_tpu.serving.topology import (draw_peer_graph, graph_links,
                                             materialize)
from tendermint_tpu.utils.procs import free_port_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAW = [1_000_000 // (r + 2) for r in range(1, 8)]
REHEARSAL_TIMEOUTS = {
    "timeout_propose": 3000, "timeout_propose_delta": 500,
    "timeout_prevote": 1000, "timeout_prevote_delta": 500,
    "timeout_precommit": 1000, "timeout_precommit_delta": 500,
    "timeout_commit": 200, "skip_timeout_commit": False}
DELAY_MS = [[0, 4, 10], [4, 0, 8], [10, 8, 0]]     # wan3 at a tenth


@pytest.fixture(autouse=True)
def _telemetry_as_found():
    """small_net's `telemetry=False` reaches the validators the caller
    hosts, whose Node switches telemetry off for the whole process: the
    tests that run after this file in the same worker find the switch
    as these found it."""
    from tendermint_tpu import telemetry
    was = telemetry.enabled()
    yield
    telemetry.set_enabled(was)


def small_net(**kw) -> Topology:
    """7 validators: 2 hosted by the caller, 5 in 2 workers."""
    args = dict(n_validators=7, chain_id="workers-test", powers=LAW,
                key_seed=31, dial_k=3, n_workers=2, in_process=(1, 4),
                rpc_validators=(1, 4), in_memory=True, regions=3,
                region_delay_ms=DELAY_MS, region_jitter_ms=2,
                addr_book_strict=True, verifier_backend="python",
                telemetry=False, log_level="error", fast_timeouts=False,
                timeouts=REHEARSAL_TIMEOUTS, max_seconds=90)
    args.update(kw)
    return Topology(**args)


def _wait(cond, timeout=30.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


# ------------------------------------------------------------ the draw

@pytest.mark.parametrize("n,k", [(7, 3), (100, 3), (100, 5), (20, 1)])
def test_peer_graph_is_connected_and_the_same_draw_twice(n, k):
    dials = draw_peer_graph(n, k, seed=2**31 + 5)
    assert dials == draw_peer_graph(n, k, seed=2**31 + 5)
    assert all(len(set(row)) == k and i not in row
               for i, row in enumerate(dials))
    links = graph_links(dials)
    assert all(a < b for a, b in links) and len(set(links)) == len(links)
    near = {i: set() for i in range(n)}
    for a, b in links:
        near[a].add(b)
        near[b].add(a)
    seen, edge = {0}, [0]
    while edge:
        for j in near[edge.pop()] - seen:
            seen.add(j)
            edge.append(j)
    assert len(seen) == n


def test_another_seed_is_another_draw_and_k_is_held_to_its_range():
    assert draw_peer_graph(100, 3, 1) != draw_peer_graph(100, 3, 2)
    with pytest.raises(ValueError):
        draw_peer_graph(4, 4, 1)


# ------------------------------------------------- what materialize writes

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("net"))
    topo = small_net(base_port=free_port_block(14))
    return topo, out, materialize(topo, out)


def test_powers_are_in_every_genesis_and_keys_come_from_the_seed(tree,
                                                                 tmp_path):
    from tendermint_tpu.types import GenesisDoc
    topo, out, _specs = tree
    gens = [GenesisDoc.load(os.path.join(out, f"val{k}", "config",
                                         "genesis.json")) for k in range(7)]
    assert all([v.power for v in g.validators] == LAW for g in gens)
    assert len({json.dumps(g.to_obj(), sort_keys=True) for g in gens}) == 1
    again = str(tmp_path / "again")
    materialize(small_net(base_port=topo.base_port), again)
    assert GenesisDoc.load(os.path.join(
        again, "val3", "config", "genesis.json")).to_obj() == \
        gens[0].to_obj()
    other = str(tmp_path / "other")
    materialize(small_net(base_port=topo.base_port, key_seed=32), other)
    assert GenesisDoc.load(os.path.join(
        other, "val3", "config", "genesis.json")).validators[0].pubkey != \
        gens[0].validators[0].pubkey


def test_each_validator_dials_its_own_k_of_the_draw(tree):
    from tendermint_tpu.config import default_config
    from tendermint_tpu.p2p import NodeKey
    topo, out, _specs = tree
    dials = draw_peer_graph(7, 3, topo.key_seed)
    ids = [NodeKey.load(os.path.join(out, f"val{k}", "config",
                                     "node_key.json")).id()
           for k in range(7)]
    for k in range(7):
        cfg = default_config(os.path.join(out, f"val{k}"))
        peers = cfg.p2p.persistent_peers.split(",")
        assert peers == [f"{ids[j]}@127.0.0.1:{topo.base_port + 2 * j}"
                         for j in dials[k]]
        assert cfg.p2p.addr_book_strict is True
        assert cfg.p2p.max_num_peers == 50
        assert cfg.p2p.region == k % 3
        assert cfg.p2p.region_delay_ms == [float(x)
                                           for x in DELAY_MS[k % 3]]
        assert cfg.p2p.region_jitter_ms == 2.0
        assert cfg.base.verifier_backend == "python"
        assert cfg.base.telemetry is False
        assert cfg.consensus.timeout_commit == 200
        assert cfg.consensus.timeout_propose == 3000


def test_workers_host_what_the_caller_does_not(tree):
    topo, out, specs = tree
    assert [s.kind for s in specs] == ["worker", "worker"]
    assert [list(s.nodes) for s in specs] == [["val0", "val3", "val6"],
                                              ["val2", "val5"]]
    assert topo.hosted_by_worker() == [[0, 3, 6], [2, 5]]
    for s in specs:
        assert s.argv[s.argv.index("--nodes") + 1] == ",".join(s.nodes)
        assert "--in-memory" in s.argv and "--rpc" not in s.argv
        assert float(s.argv[s.argv.index("--max-seconds") + 1]) == 90


def test_todays_callers_get_todays_tree(tmp_path):
    """The new fields' defaults: equal stake, the full mesh, a process
    a validator, a lax address book, no region."""
    from tendermint_tpu.config import default_config
    from tendermint_tpu.types import GenesisDoc
    topo = Topology(n_validators=3, base_port=free_port_block(6))
    specs = materialize(topo, str(tmp_path))
    assert [s.kind for s in specs] == ["validator"] * 3
    gen = GenesisDoc.load(str(tmp_path / "val0" / "config" /
                              "genesis.json"))
    assert [v.power for v in gen.validators] == [10, 10, 10]
    cfg = default_config(str(tmp_path / "val1"))
    assert len(cfg.p2p.persistent_peers.split(",")) == 2
    assert cfg.p2p.addr_book_strict is False
    assert cfg.p2p.region_delay_ms == [] and cfg.base.telemetry is True
    assert cfg.consensus.timeout_commit == 100      # FAST_TIMEOUTS


# ------------------------------------------------------ delay by region

class _Inner:
    """A link that seals a burst as its frames joined."""

    def __init__(self):
        self.sealed = []

    def seal_frames(self, chunks):
        self.sealed.append(list(chunks))
        return b"|".join(chunks)

    def feed_wire(self, data):
        return [data] if data else []

    def close(self):
        pass


class _Loop:
    """call_later recorded, fired by hand."""

    def __init__(self):
        self.timers = []

    def call_later(self, delay, fn, owner="loop"):
        self.timers.append((delay, fn))


def _delayed(delay_ms, jitter_ms, seed=5):
    from tendermint_tpu.p2p.fuzz import FuzzConfig, FuzzedLink
    inner, loop, out = _Inner(), _Loop(), []
    link = FuzzedLink(inner, FuzzConfig(
        mode="delay", delay_s=delay_ms / 1e3, jitter_s=jitter_ms / 1e3,
        seed=seed))
    link.attach_loop(loop, out.append)
    return link, inner, loop, out


@pytest.mark.parametrize("delay_ms,jitter_ms", [(40, 20), (100, 20),
                                                (80, 0), (4, 2)])
def test_a_set_delay_holds_every_burst_within_its_jitter(delay_ms,
                                                         jitter_ms):
    link, inner, loop, out = _delayed(delay_ms, jitter_ms)
    assert link.seal_frames([b"a", b"b"]) == b""    # held, not returned
    assert inner.sealed == [[b"a", b"b"]]           # but sealed at once
    (hold, fire), = loop.timers
    # hold = (now + delay) - now: a float's last place under the delay
    assert delay_ms / 1e3 - 1e-9 <= hold <= \
        (delay_ms + jitter_ms) / 1e3 + 1e-3
    assert out == []
    fire()
    assert out == [b"a|b"]


def test_held_bursts_leave_in_the_order_they_were_sealed():
    link, _inner, loop, out = _delayed(40, 20)
    for i in range(50):
        link.seal_frames([b"%d" % i])
    holds = [t[0] for t in loop.timers]
    assert all(0.04 - 1e-9 <= x <= 0.0611 for x in holds)
    # whichever timer fires first, the oldest burst goes first
    for _delay, fire in reversed(loop.timers):
        fire()
    assert out == [b"%d" % i for i in range(50)]


def test_no_delay_set_or_no_loop_attached_returns_the_bytes():
    from tendermint_tpu.p2p.fuzz import FuzzConfig, FuzzedLink
    inner = _Inner()
    link = FuzzedLink(inner, FuzzConfig(mode="delay", prob_drop_rw=0.0))
    assert link.seal_frames([b"x"]) == b"x"
    link2, _inner, loop, _out = _delayed(0, 20)
    assert link2.seal_frames([b"y"]) == b"y" and not loop.timers


def test_a_switch_delays_by_the_peers_region_and_only_then():
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.p2p import NodeInfo, NodeKey, Switch
    from tendermint_tpu.p2p.fuzz import FuzzedLink
    from tendermint_tpu.types import PrivKey
    key = NodeKey(PrivKey.generate(b"\x01" * 32))

    def info(seed, other):
        return NodeInfo(pubkey=PrivKey.generate(seed).pubkey.ed25519,
                        other=other)
    cfg = P2PConfig(region=1, region_delay_ms=[40.0, 0.0, 80.0],
                    region_jitter_ms=20.0, region_delay_seed=9)
    sw = Switch(cfg, key, NodeInfo(pubkey=key.pubkey))
    inner = _Inner()
    far = sw._delay_link(inner, info(b"\x02" * 32, ["region=2"]))
    assert isinstance(far, FuzzedLink) and far.link is inner
    assert (far.config.mode, far.config.delay_s, far.config.jitter_s) == \
        ("delay", 0.08, 0.02)
    assert sw._delay_link(inner, info(b"\x03" * 32, ["region=0"])
                          ).config.delay_s == 0.04
    # its own region, a peer that names none, one out of range: as is
    for other in (["region=1"], [], ["region=7"], ["region=x"]):
        assert sw._delay_link(inner, info(b"\x04" * 32, other)) is inner
    assert Switch(P2PConfig(), key, NodeInfo(pubkey=key.pubkey)
                  )._region_delay_ms == []


# ------------------------------------- a worker net, and how it ends

def test_worker_net_commits_reports_and_stops_clean(tmp_path):
    d = Deployment(small_net(), str(tmp_path / "net"), max_restarts=0)
    links = d.declared_links()
    assert links == graph_links(draw_peer_graph(7, 3, 31))
    d.start()
    pids = [p.pid for p in d._procs.values()]
    try:
        assert [n.with_rpc for n in d.local_nodes] == [True, True]
        assert _wait(lambda: min(n.height for n in d.local_nodes) >= 3)
        def held():
            """Every worker's report, once every validator holds the
            peers it dials (a dial that came too early is made again
            after a second)."""
            rep = d.ask({"cmd": "report", "last": 4}, 5.0)
            docs = {}
            for r in rep.values():
                docs.update(r["nodes"])
            ok = len(rep) == 2 and all(len(doc["peers"]) >= 3
                                       for doc in docs.values())
            return (rep, docs) if ok else None
        assert _wait(lambda: held() is not None, timeout=20.0, step=0.5)
        rep, nodes = held()
        assert sorted(rep) == ["worker0", "worker1"]
        assert all(r["cpu_s"] > 0 for r in rep.values())
        assert sorted(nodes) == ["val0", "val2", "val3", "val5", "val6"]
        assert all(doc["height"] >= 2 and not doc["banned"]
                   for doc in nodes.values())
        # all seven agree where all seven are
        from tendermint_tpu.serving.worker import node_report
        for k, node in zip((1, 4), d.local_nodes):
            nodes[f"val{k}"] = node_report(node, 4)
        common = str(min(doc["height"] for doc in nodes.values()) - 1)
        assert len({tuple(doc["hashes"][common])
                    for doc in nodes.values() if common in doc["hashes"]}) \
            == 1
        # a stake of its own: the proposers rotate by it
        powers = sorted(v.voting_power for v in
                        d.local_nodes[0].consensus.state.validators.validators)
        assert powers == sorted(LAW)
    finally:
        t0 = time.monotonic()
        d.stop(grace_s=5.0)
    assert time.monotonic() - t0 < 12.0
    assert not any(_alive(pid) for pid in pids)
    assert d.local_nodes == []


_PARENT = r"""
import sys, time
sys.path.insert(0, {repo!r})
from tendermint_tpu.utils.log import setup_logging
setup_logging("error")
from tests.test_serving_workers import small_net
from tendermint_tpu.serving import Deployment
d = Deployment(small_net(max_seconds={max_seconds}, in_process=()),
               {out!r}, max_restarts=0)
d.start()
print(" ".join(str(p.pid) for p in d._procs.values()), flush=True)
time.sleep(120)
"""


def _start_parent(tmp_path, max_seconds):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    parent = subprocess.Popen(
        [sys.executable, "-c", _PARENT.format(
            repo=REPO, out=str(tmp_path / "net"), max_seconds=max_seconds)],
        stdout=subprocess.PIPE, env=env, cwd=REPO)
    pids = [int(x) for x in parent.stdout.readline().split()]
    assert len(pids) == 2 and all(_alive(p) for p in pids)
    return parent, pids


def test_workers_are_gone_within_5_s_of_their_parents_sigkill(tmp_path):
    parent, pids = _start_parent(tmp_path, 90)
    try:
        os.kill(parent.pid, signal.SIGKILL)
        parent.wait(timeout=5)
        t0 = time.monotonic()
        assert _wait(lambda: not any(_alive(p) for p in pids), timeout=5.0)
        assert time.monotonic() - t0 < 5.0
        # and nothing else of the run is left: every process that
        # names the net's directory is gone
        got = subprocess.run(["pgrep", "-f", str(tmp_path / "net")],
                             capture_output=True, text=True)
        assert got.stdout.split() == []
    finally:
        for p in [parent.pid] + pids:
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass


def test_a_worker_leaves_at_its_max_seconds(tmp_path):
    parent, pids = _start_parent(tmp_path, 3)
    try:
        assert _wait(lambda: not any(_alive(p) for p in pids), timeout=8.0)
        assert parent.poll() is None        # the parent did nothing for it
    finally:
        parent.kill()
        parent.wait(timeout=5)
