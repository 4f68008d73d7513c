"""True multi-process testnet e2e — the reference's dockerized p2p tests
(test/p2p/{basic,atomic_broadcast}/test.sh) in-repo: `testnet` writes the
file tree, three SEPARATE OS processes run `cli node` over real TCP
sockets, a transaction enters via one node's RPC and must reach every
node's app state (atomic broadcast)."""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port_block(k):
    from tendermint_tpu.utils.procs import free_port_block
    return free_port_block(k)


def _node_env():
    from tendermint_tpu.utils.procs import node_child_env
    return node_child_env(REPO)


def test_three_process_testnet_atomic_broadcast(tmp_path):
    net = str(tmp_path / "net")
    n = 3
    base = _free_port_block(2 * n)
    r = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--n", str(n), "--output", net, "--base-port", str(base),
         "--chain-id", "e2e-net"],
        env=_node_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr

    # test-speed consensus timeouts for every node
    for i in range(n):
        cfg_path = os.path.join(net, f"node{i}", "config", "config.json")
        cfg = json.load(open(cfg_path))
        cfg["consensus"].update({
            "timeout_propose": 400, "timeout_propose_delta": 100,
            "timeout_prevote": 200, "timeout_prevote_delta": 100,
            "timeout_precommit": 200, "timeout_precommit_delta": 100,
            "timeout_commit": 100})
        json.dump(cfg, open(cfg_path, "w"))

    procs = []
    logs = []
    try:
        for i in range(n):
            log = open(os.path.join(net, f"node{i}.log"), "w+")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.cli",
                 "--home", os.path.join(net, f"node{i}"),
                 "node", "--p2p", "--no-fast-sync",
                 "--rpc-laddr", f"tcp://127.0.0.1:{base + 2 * i + 1}",
                 "--max-seconds", "600"],
                env=_node_env(), stdout=log, stderr=subprocess.STDOUT))

        from tendermint_tpu.rpc.client import JSONRPCClient
        clients = [JSONRPCClient(f"http://127.0.0.1:{base + 2 * i + 1}")
                   for i in range(n)]

        def wait_all(pred, timeout_s, what):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if all(p.poll() is None for p in procs):
                    try:
                        if all(pred(c) for c in clients):
                            return
                    except Exception:
                        pass
                else:
                    break
                time.sleep(0.5)
            for i, log in enumerate(logs):
                log.flush()
                log.seek(0)
                tail = log.read()[-1500:]
                print(f"--- node{i} log tail ---\n{tail}", file=sys.stderr)
            raise AssertionError(
                f"{what}: procs alive="
                f"{[p.poll() is None for p in procs]}")

        # all three nodes commit blocks (basic connectivity + consensus)
        wait_all(lambda c: c.call("status")["latest_block_height"] >= 2,
                 120, "no 3-node consensus progress")

        # atomic broadcast: tx via node1, state visible on ALL nodes
        res = clients[1].call("broadcast_tx_commit", tx=b"e2e=ok".hex())
        assert res["deliver_tx"]["code"] == 0
        h_commit = res["height"]

        def sees_tx(c):
            if c.call("status")["latest_block_height"] < h_commit:
                return False
            q = c.call("abci_query", data=b"e2e".hex())
            return bytes.fromhex(q["response"]["value"] or "") == b"ok"

        wait_all(sees_tx, 60, "tx did not reach every node's app state")

        # all nodes agree on the block at the commit height
        hashes = {c.call("block", height=h_commit)["block_meta"]
                  ["block_id"]["hash"] for c in clients}
        assert len(hashes) == 1
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()


def test_killed_node_fast_syncs_back(tmp_path):
    """The reference's test/p2p/fast_sync/test.sh: kill one of three
    nodes, let the others advance, restart it WITH fast-sync — it must
    catch up to the live chain and keep following it."""
    net = str(tmp_path / "net")
    n = 4  # kill 1 of 4: the rest hold 30/40 > 2/3 (2 of 3 would be
    # exactly 2/3, which is NOT a supermajority)
    base = _free_port_block(2 * n)
    r = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--n", str(n), "--output", net, "--base-port", str(base),
         "--chain-id", "e2e-sync"],
        env=_node_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    for i in range(n):
        cfg_path = os.path.join(net, f"node{i}", "config", "config.json")
        cfg = json.load(open(cfg_path))
        cfg["consensus"].update({
            "timeout_propose": 400, "timeout_propose_delta": 100,
            "timeout_prevote": 200, "timeout_prevote_delta": 100,
            "timeout_precommit": 200, "timeout_precommit_delta": 100,
            "timeout_commit": 100})
        json.dump(cfg, open(cfg_path, "w"))

    def spawn(i, fast_sync):
        log = open(os.path.join(net, f"node{i}.log"), "a+")
        args = [sys.executable, "-m", "tendermint_tpu.cli",
                "--home", os.path.join(net, f"node{i}"),
                "node", "--p2p",
                "--rpc-laddr", f"tcp://127.0.0.1:{base + 2 * i + 1}",
                "--max-seconds", "600"]
        if not fast_sync:
            args.append("--no-fast-sync")
        return subprocess.Popen(args, env=_node_env(), stdout=log,
                                stderr=subprocess.STDOUT), log

    from tendermint_tpu.rpc.client import JSONRPCClient
    clients = [JSONRPCClient(f"http://127.0.0.1:{base + 2 * i + 1}")
               for i in range(n)]

    def height_of(c, default=-1):
        try:
            return c.call("status")["latest_block_height"]
        except Exception:
            return default

    def wait(pred, timeout_s, what, procs):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if pred():
                return
            assert all(p.poll() is None for p in procs), f"{what}: node died"
            time.sleep(0.5)
        raise AssertionError(what)

    procs_logs = [spawn(i, fast_sync=False) for i in range(n)]
    procs = [p for p, _ in procs_logs]
    try:
        wait(lambda: all(height_of(c) >= 2 for c in clients), 120,
             "initial 3-node consensus", procs)

        # kill node3 hard; the remaining 30/40 power keeps committing.
        # Budget note: 30/40 is the MINIMAL supermajority — every
        # height needs all three survivors in lockstep, so on an
        # oversubscribed 1-core host each commit can take tens of
        # seconds of round churn; the generous budget de-flakes the
        # phase without weakening what it asserts (4 net-new heights).
        h_dead = height_of(clients[3], default=0)  # read BEFORE the kill
        procs[3].kill()
        procs[3].wait(timeout=10)
        wait(lambda: all(height_of(c) >= h_dead + 4
                         for c in clients[:3]), 240,
             "3-node supermajority progress", procs[:3])

        # restart node3 with fast-sync: must catch up and keep following
        procs_logs[3] = spawn(3, fast_sync=True)
        procs[3] = procs_logs[3][0]
        target = max(height_of(c) for c in clients[:3])
        wait(lambda: height_of(clients[3]) >= target, 180,
             f"fast-sync catchup to {target}", procs)
        # ...and participates in NEW heights after catching up
        wait(lambda: height_of(clients[3]) >= target + 2, 120,
             "post-sync liveness", procs)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for _, log in procs_logs:
            log.close()


def test_unknown_validator_removal_rejected_not_halting(tmp_path):
    """A val tx removing an UNKNOWN validator must be rejected by the
    app at DeliverTx (persistent_dummy's updateValidator guard) so the
    invalid update never reaches EndBlock — one unauthenticated
    broadcast_tx must NOT halt the network. The node keeps committing.
    (The halt-on-ApplyBlockError path itself stays covered by
    test_consensus.test_invalid_app_validator_update_fails_loudly,
    which injects a bad update behind the app's guard.)"""
    home = str(tmp_path / "node")
    port = _free_port_block(1)
    r = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home,
         "init"], env=_node_env(), capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 0, r.stderr
    # init writes keys+genesis but no config.json; create one with
    # test-speed timeouts
    from tendermint_tpu.config import default_config, save_config
    cfg = default_config(home)
    cfg.consensus.timeout_propose = 400
    cfg.consensus.timeout_propose_delta = 100
    cfg.consensus.timeout_prevote = 200
    cfg.consensus.timeout_prevote_delta = 100
    cfg.consensus.timeout_precommit = 200
    cfg.consensus.timeout_precommit_delta = 100
    cfg.consensus.timeout_commit = 100
    save_config(cfg)

    log = open(os.path.join(home, "node.log"), "w+")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home,
         "node", "--rpc-laddr", f"tcp://127.0.0.1:{port}",
         "--max-seconds", "120"],
        env=_node_env(), stdout=log, stderr=subprocess.STDOUT)
    try:
        from tendermint_tpu.rpc.client import JSONRPCClient
        c = JSONRPCClient(f"http://127.0.0.1:{port}")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if c.call("status")["latest_block_height"] >= 1:
                    break
            except Exception:
                time.sleep(0.5)
        else:
            raise AssertionError("node never started committing")

        ghost = "22" * 32
        res = c.call("broadcast_tx_commit",
                     tx=f"val:{ghost}/0".encode().hex())
        # CheckTx passes (format is fine), DeliverTx rejects: the app
        # refuses to remove a validator it doesn't know
        assert res["check_tx"].get("code", 0) == 0, res
        assert res["deliver_tx"]["code"] == 2, res
        assert "unknown validator" in res["deliver_tx"].get("log", "")

        # ...and the chain keeps committing afterwards
        h0 = c.call("status")["latest_block_height"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if c.call("status")["latest_block_height"] > h0:
                break
            time.sleep(0.3)
        else:
            raise AssertionError("node stopped committing after bad val tx")
        assert proc.poll() is None, "node process died on a rejected tx"
        log.flush()
        log.seek(0)
        assert "CONSENSUS FAILURE" not in log.read()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        log.close()


def test_node_process_exits_on_consensus_failure(tmp_path):
    """The reference panics the process on an ApplyBlock failure; our
    node must print CONSENSUS FAILURE and exit code 1 — not sit frozen.
    The KVStore app's DeliverTx guard normally keeps invalid updates
    from ever reaching the core, so this drives the halt path behind
    the guard with the TM_KVSTORE_UNSAFE_VAL_UPDATES fail-point."""
    home = str(tmp_path / "node")
    port = _free_port_block(1)
    env = _node_env()
    env["TM_KVSTORE_UNSAFE_VAL_UPDATES"] = "1"
    r = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home,
         "init"], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    from tendermint_tpu.config import default_config, save_config
    cfg = default_config(home)
    cfg.consensus.timeout_propose = 400
    cfg.consensus.timeout_propose_delta = 100
    cfg.consensus.timeout_prevote = 200
    cfg.consensus.timeout_prevote_delta = 100
    cfg.consensus.timeout_precommit = 200
    cfg.consensus.timeout_precommit_delta = 100
    cfg.consensus.timeout_commit = 100
    save_config(cfg)

    log = open(os.path.join(home, "node.log"), "w+")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home,
         "node", "--rpc-laddr", f"tcp://127.0.0.1:{port}",
         "--max-seconds", "120"],
        env=env, stdout=log, stderr=subprocess.STDOUT)
    try:
        from tendermint_tpu.rpc.client import JSONRPCClient, RPCClientError
        c = JSONRPCClient(f"http://127.0.0.1:{port}")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if c.call("status")["latest_block_height"] >= 1:
                    break
            except Exception:
                time.sleep(0.5)
        else:
            raise AssertionError("node never started committing")

        ghost = "22" * 32
        try:
            res = c.call("broadcast_tx_sync",
                         tx=f"val:{ghost}/0".encode().hex())
        except (RPCClientError, OSError):
            # the single-writer drain may run propose->commit->apply
            # INLINE on the RPC handler's own thread, so the
            # ApplyBlockError can surface as this call's error reply —
            # equally valid; the process must still die below
            res = None
        if res is not None:
            assert res.get("code", 0) == 0, f"tx rejected: {res}"

        rc = proc.wait(timeout=60)
        assert rc == 1, f"expected loud exit 1, got {rc}"
        log.flush()
        log.seek(0)
        out = log.read()
        assert "CONSENSUS FAILURE" in out
        assert "removing unknown validator" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        log.close()
