"""Deployment-driver smoke (ISSUE 19 satellite 5): a declarative
Topology becomes a real 3-process net — two validators + one keyless
edge replica over real TCP — which boots, commits, certifies, serves a
client-verified proven read, survives a process crash via the
supervisor, and tears down leak-clean."""

import os
import time

import pytest

from tendermint_tpu.serving import Deployment, Topology


def _wait(cond, timeout=90.0, step=0.3):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


def test_deployment_smoke_boot_certify_read_crash_restart(tmp_path):
    topo = Topology(kind="validators", n_validators=2, n_replicas=1,
                    chain_id="deploy-smoke", max_seconds=300,
                    env={"TM_TPU_STATE_TREE": "on"})
    out = str(tmp_path / "net")
    d = Deployment(topo, out, max_restarts=2)

    # the trust-model floor on disk: replicas carry NO signing key
    for spec in d.specs:
        pv = os.path.join(spec.home, "config", "priv_validator.json")
        assert os.path.exists(pv) == (spec.kind == "validator"), \
            spec.name

    d.start()
    try:
        # validators commit 3 heights over real sockets
        d.wait_height(3, timeout_s=120)

        # the replica (fast-sync follower) certifies from its own
        # stores and stamps every response with honest staleness
        rep = d.clients(kind="replica")[0]

        def certified(h):
            try:
                return rep.call("status")["edge"][
                    "certified_height"] >= h
            except OSError:
                return False
        assert _wait(lambda: certified(2)), d.log_tail("replica0")

        # write through a validator, read PROVEN through the replica,
        # verify client-side from the genesis valset — zero trust in
        # the replica (every replica-served read is verifiable)
        val = d.clients(kind="validator")[0]
        val.call("broadcast_tx_commit", tx=b"dk=dv".hex())
        # the header AFTER the write's block binds its value: a read
        # served at the write's own height proves the state before it
        wrote_by = val.call("status")["latest_block_height"]
        assert _wait(lambda: certified(wrote_by + 1)), \
            d.log_tail("replica0")
        doc = rep.call("replica_read", key=b"dk".hex())
        assert bytes.fromhex(doc["value"]) == b"dv"
        assert doc["value_proof"] is not None
        assert doc["edge"]["certified_height"] >= doc["height"]
        from tendermint_tpu.lite.certifier import ContinuousCertifier
        from tendermint_tpu.shard.reads import CertifiedReader, _genesis_valset
        from tendermint_tpu.types import GenesisDoc
        gen = GenesisDoc.load(os.path.join(
            d.spec("replica0").home, "config", "genesis.json"))
        cert = ContinuousCertifier(gen.chain_id, _genesis_valset(gen))
        CertifiedReader.verify(doc, cert)
        assert cert.certified_height >= doc["height"]

        # healthz folds the edge verdict for load balancers
        hz = rep.call("healthz")
        assert hz["edge"]["role"] == "replica"
        assert hz["edge"]["lag"] <= hz["edge"]["max_lag"]

        # crash/restart: hard-kill the replica; the supervisor
        # respawns it (same argv) and it certifies again
        d.kill("replica0")
        assert _wait(lambda: d.restarts.get("replica0", 0) >= 1,
                     timeout=30)
        assert _wait(lambda: d.alive("replica0"), timeout=30)
        assert _wait(lambda: certified(2), timeout=90), \
            d.log_tail("replica0")
        assert not d.dead
    finally:
        d.stop()

    # leak-clean teardown: no live processes, logs closed, tree gone
    assert all(p.poll() is not None for p in d._procs.values())
    assert not d._logs
    assert not os.path.exists(out)
