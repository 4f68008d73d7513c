"""Validator-scale adversarial plane tests (ISSUE 11): churn through
real EndBlock deltas, geo-profiled links, continuous lite
certification as a run invariant, statesync joining a churned net, and
the long seeded soaks. The tier-1 members stay at small n (seconds on
the 1-core CI host); the 32-validator acceptance run and the soaks are
slow-marked."""

import pytest

from tendermint_tpu.chaos.runner import run_chaos, scale_spec

# Tier-1 churn+geo scenario: small enough for seconds, rich enough to
# cross every churn op and certify continuously through the deltas.
CHURN_SMOKE_SPEC = {
    "drop": 0.02,
    "delay": 0.05,
    "delay_steps": [1, 2],
    "geo": {"profile": "wan3"},
    "churn": {"start_height": 2, "every_heights": 2, "standby": 2,
              "ops": ["join", "leave", "stake"], "max_events": 4},
    # the relay's drops are final (no reactor re-gossip), and
    # WAN-calibrated rounds are long — a dropped precommit would wedge
    # a height for tens of steps, so the smoke opts into the
    # deterministic stall re-delivery like every scale spec
    "stall_assist": True,
}


def test_scale_spec_shape():
    from tendermint_tpu.chaos.schedule import GEO_PROFILES
    full = scale_spec(32)
    assert full["geo"]["profile"] == "wan3"
    # bandwidth caps carry the same per-node-pair budget at every n:
    # the 4-node calibration scaled by (n/4)^2
    base = GEO_PROFILES["wan3"]["bandwidth_msgs"]
    assert full["geo"]["bandwidth_msgs"] == \
        [[c * 64 for c in row] for row in base]
    assert full["churn"]["ops"] == ["join", "leave", "stake"]
    assert full["churn"]["standby"] == 2
    assert full["stall_assist"] is True
    trimmed = scale_spec(128, full_churn=False)
    assert trimmed["churn"]["ops"] == ["join", "leave"]
    assert trimmed["churn"]["standby"] == 8
    assert trimmed["churn"]["every_heights"] == 1


def test_churn_geo_smoke_certifies_every_height():
    """Tier-1 smoke: 6 nodes (4 genesis validators + 2 standby), wan3
    geo links, drop/delay faults, and a full join/leave/stake churn
    cycle applied THROUGH consensus — zero invariant violations and
    every committed height continuously lite-certified across the
    valset deltas."""
    r = run_chaos(spec=CHURN_SMOKE_SPEC, seed=7, n=6, target_height=6,
                  max_steps=600, settle_steps=20)
    assert r["violations"] == []
    assert r["n_genesis_validators"] == 4
    # the chain kept committing while the set rotated under it
    assert r["max_height"] >= 6
    churn = r["churn"]
    assert churn["churn_join"] >= 1
    assert churn["churn_leave"] >= 1
    assert churn["churn_stake"] >= 1
    lite = r["lite"]
    assert lite["active"], "lite certification halted mid-run"
    assert lite["certified_height"] == r["max_height"]
    # the certifier really crossed valset deltas (joins/leaves/stake
    # changes each rewrite the valset hash)
    assert lite["valset_updates"] >= 2
    assert lite["valset_size_max"] > lite["valset_size_min"]
    # geo links actually shaped traffic
    f = r["faults_injected"]
    assert f.get("geo_drop", 0) + f.get("geo_throttle", 0) >= 1
    assert r["fault_log_sha256"]


def test_monitor_lite_flags_uncertifiable_commit():
    """The certified invariant is loud: a provider serving a commit
    the certifier cannot verify (forged signatures) must record a
    'certified' violation and halt certification."""
    import types

    from tendermint_tpu.chaos.monitor import InvariantMonitor
    from tests.test_lite import CHAIN, ValKeys

    vk = ValKeys(4)
    good = vk.sign_header(1)
    bad = vk.sign_header(2)
    for pc in bad.signed_header.commit.precommits:
        if pc is not None:
            pc.signature = b"\x00" * 64

    m = InvariantMonitor()
    m.attach_lite(CHAIN, vk.valset,
                  {1: good, 2: bad}.get)
    blk = types.SimpleNamespace()
    blk.header = types.SimpleNamespace(height=2)
    blk.evidence = types.SimpleNamespace(evidence=[])
    blk.hash = lambda: b"\xaa" * 32
    m._on_commit(1, 0, blk)       # max_height -> 2
    m._advance_lite(step=1)
    assert [v["invariant"] for v in m.violations] == ["certified"]
    assert m.violations[0]["height"] == 2
    assert m.lite.certified_height == 1
    # halted: no further checks after the loud failure
    checks = dict(m.checks)
    m._advance_lite(step=2)
    assert m.checks == checks


# ------------------------------------------------------------- slow --

@pytest.mark.slow
def test_chaos_32_validators_churn_geo_acceptance(tmp_path):
    """THE ISSUE 11 acceptance run: a 32-validator ChaosNet with
    valset churn (>=1 join, >=1 leave, >=1 stake change applied
    through EndBlock), the wan3 geo latency profile, and injected
    faults — 0 invariant violations, every height continuously
    lite-certified against the churning valset."""
    spec = scale_spec(32)
    r = run_chaos(spec=spec, seed=42, n=32,
                  workdir=str(tmp_path / "net32"),
                  target_height=4, max_steps=400, settle_steps=10)
    assert r["violations"] == []
    assert r["n_nodes"] == 32
    churn = r["churn"]
    assert churn["churn_join"] >= 1
    assert churn["churn_leave"] >= 1
    assert churn["churn_stake"] >= 1
    f = r["faults_injected"]
    assert f.get("drop", 0) >= 1 and f.get("delay", 0) >= 1
    assert f.get("geo_drop", 0) + f.get("geo_throttle", 0) >= 1
    assert f.get("crash") == 1 and f.get("restart") == 1
    lite = r["lite"]
    assert lite["active"]
    assert lite["certified_height"] == r["max_height"]
    assert lite["valset_updates"] >= 3
    assert r["fault_log_sha256"]


@pytest.mark.slow
def test_chaos_long_soak_thousands_of_faults(tmp_path):
    """Long seeded soak: thousands of faults across churn + geo +
    partitions + crashes + a byzantine window on an 8-node net — zero
    invariant violations, every injected double-sign committed as
    evidence, every height lite-certified."""
    spec = {
        "drop": 0.05,
        "delay": 0.10,
        "delay_steps": [1, 3],
        "duplicate": 0.03,
        "reorder": 0.04,
        "geo": {"profile": "wan3"},
        "churn": {"start_height": 3, "every_heights": 3, "standby": 2,
                  "ops": ["join", "leave", "stake"], "max_events": 4},
        "partitions": [{"start": 120, "stop": 170,
                        "groups": [[0, 1], [2, 3, 4, 5, 6, 7]]},
                       {"start": 900, "stop": 980,
                        "groups": [[2, 3], [0, 1, 4, 5, 6, 7]]}],
        "crashes": [{"node": 3, "after_height": 3,
                     "point": "consensus.before_save_block",
                     "down_steps": 20},
                    {"node": 5, "after_height": 6,
                     "point": "execution.after_app_commit",
                     "down_steps": 15}],
        # the byzantine window sits in steady state AFTER the first
        # partition heals: equivocations at the genesis heights (while
        # WAN rounds are still long and churn txs are landing) can
        # miss the honest-capture window entirely — observed as 4
        # uncommitted double-signs with a step-30 start. Like
        # ACCEPTANCE_SPEC, the scenario phases are staggered so every
        # injected double-sign is CAPTURABLE; the oracle then insists
        # all of them commit
        "byzantine": [{"node": 1, "behavior": "equivocate",
                       "start": 190, "stop": 330}],
        "stall_assist": True,
    }
    r = run_chaos(spec=spec, seed=1234, n=8,
                  workdir=str(tmp_path / "soak"),
                  target_height=35, max_steps=2600, settle_steps=60)
    assert r["violations"] == []
    assert r["faults_injected_total"] >= 2000, r["faults_injected"]
    f = r["faults_injected"]
    for kind in ("drop", "delay", "duplicate", "reorder", "partition",
                 "heal", "crash", "restart", "equivocation",
                 "churn_join"):
        assert f.get(kind, 0) >= 1, f"{kind} never fired: {f}"
    ev = r["evidence"]
    assert ev["committed"] == ev["injected_double_signs"] > 0
    lite = r["lite"]
    assert lite["active"]
    assert lite["certified_height"] >= r["max_height"] - 1
    assert lite["valset_updates"] >= 2


@pytest.mark.slow
def test_statesync_joins_net_whose_valset_rotated(tmp_path, monkeypatch):
    """Statesync under churn (ISSUE 11 tentpole): a fresh node
    restores from a snapshot taken BEFORE the valset rotated, then
    fast-syncs the tail across the EndBlock deltas — and the restored
    chain lite-certifies from the snapshot's valset to the frontier
    through every delta."""
    import time as _time

    monkeypatch.setenv("TM_TPU_SNAPSHOT_INTERVAL", "5")
    monkeypatch.setenv("TM_TPU_SNAPSHOT_KEEP", "2")
    from tendermint_tpu.chaos.runner import ChaosNet
    from tendermint_tpu.lite import ContinuousCertifier
    from tendermint_tpu.lite.types import FullCommit, SignedHeader
    from tests.test_statesync import _fresh_side, _serving_switch, _wait
    from tendermint_tpu.p2p.test_util import connect_switches

    spec = {
        "churn": {"start_height": 5, "every_heights": 2, "standby": 1,
                  "ops": ["join", "stake"], "max_events": 2},
        "stall_assist": True,
    }
    net = ChaosNet(str(tmp_path / "src-net"), spec, seed=3, n=4,
                   chain_id="ss-net")
    net.start()
    try:
        net.run(9, max_steps=800, settle_steps=10)
        rep = net.report()
        assert rep["violations"] == []
        assert rep["churn"]["events"] >= 2
        node0 = net.nodes[0]
        snap_heights = node0.snapshot_store.list_heights()
        assert snap_heights, "source produced no snapshots"
        snap_h = max(h for h in snap_heights if h <= 5)
        # the valset REALLY rotated after the snapshot height
        vals_at_snap = node0.state_store.load_validators(snap_h)
        vals_at_top = node0.state_store.load_validators(
            node0.block_store.height())
        assert vals_at_snap.hash() != vals_at_top.hash()
        # serve only the pre-rotation snapshot: the joiner must cross
        # the churn through the fast-synced tail, not the snapshot
        for h in snap_heights:
            if h != snap_h:
                node0.snapshot_store.delete(h)

        src = {"gen": net.gen, "cs": node0.consensus,
               "block_store": node0.block_store,
               "state_store": node0.state_store,
               "snap_store": node0.snapshot_store}
        sw_src = _serving_switch(src, b"\x31" * 32)
        new = _fresh_side(tmp_path, net.gen)
        new["sw"].start()
        connect_switches(sw_src, new["sw"])
        try:
            _wait(lambda: new["bc"].synced, 60, "never synced")
            restored = new["ss"].restored_state
            assert restored is not None
            assert restored.last_block_height == snap_h
            assert new["block_store"].base() == snap_h + 1
            top = new["block_store"].height()
            assert top >= node0.block_store.height() - 1
            # the tail crossed the rotation: the restored node's OWN
            # stores now hold the churned valsets
            assert new["state_store"].load_validators(top).hash() == \
                node0.state_store.load_validators(top).hash()

            # ...and the whole tail lite-certifies from the SNAPSHOT's
            # trusted valset across every delta, off the joiner's own
            # stores (exactly what a light client bootstrapping from
            # that snapshot would do)
            cert = ContinuousCertifier(
                "ss-net", restored.validators,
                next_height=snap_h + 1)
            for h in range(snap_h + 1, top + 1):
                meta = new["block_store"].load_block_meta(h)
                commit = new["block_store"].load_seen_commit(h) or \
                    new["block_store"].load_block_commit(h)
                vals = new["state_store"].load_validators(h)
                assert meta is not None and commit is not None
                cert.advance(FullCommit(
                    SignedHeader(meta.header, commit, meta.block_id),
                    vals))
            assert cert.certified_height == top
            assert cert.updates >= 1, "tail crossed no valset delta"
        finally:
            sw_src.stop()
            new["sw"].stop()
    finally:
        net.stop()
