"""Shard plane (ISSUE 15): N independent chains in one process behind
one front door — router determinism, shard isolation under a chaos
crash point, certified cross-shard reads (incl. forged-proof
rejection), arbitrary-order teardown vs the shared verifier, and the
per-shard observability labels (tm_shard_*, tm_rpc_call_seconds chain,
SLO chain attribution)."""

import copy
import subprocess
import sys
import time

import pytest

from tendermint_tpu import telemetry
from tendermint_tpu.shard import (
    CertifiedReader,
    ReadProofError,
    ShardSet,
)
from tendermint_tpu.shard.router import ShardMap, key_prefix


def wait_for(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


@pytest.fixture
def shard2():
    s = ShardSet(2, chain_prefix="tshard")
    s.start()
    try:
        assert wait_for(lambda: s.frontier() >= 2), s.heights()
        yield s
    finally:
        s.stop()


# ------------------------------------------------------- determinism --

def test_shard_map_is_a_pure_function_of_key_and_count():
    m = ShardMap(["a", "b", "c"])
    keys = [b"k%d" % i for i in range(256)]
    first = [m.shard_of(k) for k in keys]
    assert first == [ShardMap(["a", "b", "c"]).shard_of(k)
                     for k in keys]
    # every shard owns a piece of a modest keyspace
    assert set(first) == {0, 1, 2}
    # in range, and chain_of agrees
    assert all(0 <= i < 3 for i in first)
    assert all(m.chain_of(k) == m.chains[i]
               for k, i in zip(keys, first))


def test_shard_map_deterministic_across_processes():
    """Same key -> same shard in a DIFFERENT process: the mapping has
    no per-process state (no seed, no salt, no iteration order)."""
    keys = [b"user/%d" % i for i in range(32)]
    local = [ShardMap(["a"] * 8).shard_of(k) for k in keys]
    code = (
        "from tendermint_tpu.shard.router import ShardMap\n"
        "m = ShardMap(['a'] * 8)\n"
        "print(','.join(str(m.shard_of(b'user/%d' % i)) "
        "for i in range(32)))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, check=True, env={"JAX_PLATFORMS": "cpu",
                                      "PATH": "/usr/bin:/bin",
                                      "PYTHONPATH": "."},
        cwd=__import__("os").path.dirname(
            __import__("os").path.dirname(__file__)))
    remote = [int(x) for x in out.stdout.strip().split(",")]
    assert remote == local


def test_shard_map_stable_across_mapping_versions():
    """A version bump with the same chain list (rebalance bookkeeping,
    not a count change) moves NO key; a count change is visible via
    the version, which responses quote."""
    m1 = ShardMap(["a", "b", "c", "d"])
    m2 = m1.rebalanced(["a", "b", "c", "d"])
    assert m2.version == m1.version + 1
    keys = [b"acct-%d" % i for i in range(128)]
    assert [m1.shard_of(k) for k in keys] == \
        [m2.shard_of(k) for k in keys]
    obj = m2.to_obj()
    assert obj["version"] == 2 and obj["n_shards"] == 4
    assert len(obj["ranges"]) == 4
    assert obj["ranges"][0]["lo"] == "0" * 16


def test_key_prefix_routes_tx_and_query_identically():
    m = ShardMap(["a"] * 16)
    assert key_prefix(b"balance/7=100") == b"balance/7"
    assert key_prefix(b"no-equals-tx") == b"no-equals-tx"
    assert m.shard_of(key_prefix(b"balance/7=100")) == \
        m.shard_of(b"balance/7")


# ---------------------------------------------------------- assembly --

def test_shards_share_default_verifier_and_one_loop(shard2):
    v0, v1 = (n.verifier for n in shard2.nodes)
    assert v0 is v1, "shards must share the process-default verifier"
    assert all(n.loop is shard2.loop for n in shard2.nodes)
    assert all(not n._owns_loop for n in shard2.nodes)
    # distinct chains, distinct valsets, independent heights
    assert len(set(shard2.chains)) == 2
    pks = {n.consensus.priv_validator.pubkey.ed25519
           for n in shard2.nodes}
    assert len(pks) == 2


def test_stop_in_arbitrary_order_keeps_shared_verifier_alive():
    """Stopping shards in any order leaves the shared verifier as it
    was: a node's stop touches no verifier, whoever built it, even
    across a set_default_verifier() swap between build and stop."""
    from tendermint_tpu.models.verifier import (
        default_verifier,
        set_default_verifier,
    )
    s = ShardSet(3, chain_prefix="tdown")
    shared = s.nodes[0].verifier
    assert shared is default_verifier()
    s.start()
    try:
        assert wait_for(lambda: s.frontier() >= 1), s.heights()
        set_default_verifier(shared)  # idempotent swap, same object
        for node in (s.nodes[1], s.nodes[0], s.nodes[2]):  # odd order
            node.stop()
        # the shared verifier still verifies after every stop
        from tendermint_tpu.types.keys import PrivKey
        k = PrivKey.generate(b"\x07" * 32)
        sig = k.sign(b"still-alive")
        ok = shared.verify(
            [(k.pubkey.ed25519, b"still-alive", sig)])
        assert bool(ok.all())
    finally:
        s.nodes = []       # already stopped, arbitrary order
        s.stop()           # idempotent: loop teardown only


# --------------------------------------------------------- isolation --

@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crashed_shard_leaves_siblings_committing():
    """Chaos crash point: one shard's consensus thread dies mid-commit
    (ChaosCrash from an armed commit fail point); its height freezes
    while every sibling keeps committing."""
    from tendermint_tpu.chaos.runner import ChaosCrash
    from tendermint_tpu.utils import fail

    s = ShardSet(3, chain_prefix="tcrash")
    s.start()
    try:
        assert wait_for(lambda: s.frontier() >= 2), s.heights()
        fired = []

        def boom(name):
            fired.append(name)
            raise ChaosCrash(f"shard crash at {name}")

        # one-shot: the NEXT shard to reach its commit-critical point
        # dies mid-commit (ChaosCrash is a BaseException — it escapes
        # the state machine exactly like the chaos runner's crash
        # plane); the before_save_block abort leaves no scheduled
        # timeout behind, so that shard is halted for good
        fail.arm("consensus.before_save_block", boom)
        assert wait_for(lambda: bool(fired)), \
            "armed commit point never fired"
        h1 = {n.gen_doc.chain_id: n.height for n in s.nodes}
        # siblings commit >= 3 more heights while exactly one shard is
        # frozen — fault isolation across chains in one process
        assert wait_for(lambda: sum(
            1 for n in s.nodes
            if n.height >= h1[n.gen_doc.chain_id] + 3) == 2), \
            s.heights()
        victims = [n for n in s.nodes
                   if n.height < h1[n.gen_doc.chain_id] + 3]
        assert len(victims) == 1
        dead = victims[0]
        h_dead = dead.height
        time.sleep(0.5)
        assert dead.height == h_dead, "crashed shard kept committing"
        living = [n for n in s.nodes if n is not dead]
        assert all(n.height > h1[n.gen_doc.chain_id] + 3
                   or n.height >= h_dead for n in living)
    finally:
        fail.disarm_all()
        s.stop()


# ----------------------------------------------------- certified reads --

def test_certified_cross_shard_read_e2e(shard2):
    addr = shard2.serve()
    from tendermint_tpu.rpc.client import JSONRPCClient
    c = JSONRPCClient(f"http://{addr[0]}:{addr[1]}")

    # write keys through the ONE front door; the router splits them
    keys = [b"acct/%d" % i for i in range(8)]
    r = c.call("broadcast_tx_batch",
               txs=[(k + b"=v/" + k).hex() for k in keys])
    assert all(x["code"] == 0 for x in r["results"])
    assert r["mapping_version"] == 1
    placed = {k: shard2.router.map.chain_of(k) for k in keys}
    assert len(set(placed.values())) == 2, \
        "expected keys on both shards"
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        vals = {k: bytes.fromhex(c.call(
            "abci_query", data=k.hex())["response"]["value"] or "")
            for k in keys}
        if all(v == b"v/" + k for k, v in vals.items()):
            break
        time.sleep(0.2)
    assert all(v == b"v/" + k for k, v in vals.items()), vals

    # in-process certified reader (a client resident on shard A
    # reading shard B): advances a ContinuousCertifier per chain
    reader = shard2.reader()
    for k in keys:
        res = reader.read(k)
        assert res["value"] == b"v/" + k
        assert res["chain_id"] == placed[k]
        assert res["certified_height"] >= res["height"] > 0
    assert reader.verified_reads == len(keys)
    assert set(reader._certifiers) == set(shard2.chains)

    # a SECOND read pays only the delta since the last certified
    # height (the continuous-certification contract)
    cert = reader._certifiers[placed[keys[0]]]
    before = cert.certified_height
    res = reader.read(keys[0])
    assert res["certified_height"] >= before

    # the HTTP transport shape verifies identically
    http_reader = CertifiedReader(call=lambda m, **p: c.call(m, **p))
    res = http_reader.read(keys[0])
    assert res["value"] == b"v/" + keys[0]

    v = telemetry.value("shard_cross_reads_total",
                        {"result": "verified"})
    assert v and v >= len(keys) + 2


def test_forged_cross_shard_proof_is_rejected(shard2):
    """Forged proofs die loudly: a flipped signature bit, a truncated
    proof chain, and a wrong-chain proof each raise ReadProofError and
    do NOT advance trust."""
    from tendermint_tpu.lite.certifier import ContinuousCertifier
    from tendermint_tpu.shard import reads

    node = shard2.node_for_key(b"forge-me")
    chain = node.gen_doc.chain_id
    genesis_vals = node.state_store.load_validators(1)
    doc = reads.serve_read(node, b"forge-me", 0)
    assert doc["height"] >= 1 and doc["proof_commits"]

    # 1. tampered signature in the newest commit
    forged = copy.deepcopy(doc)
    for v in forged["proof_commits"][-1]["signed_header"]["commit"][
            "precommits"]:
        if v:
            sig = bytearray(bytes.fromhex(v["signature"]))
            sig[0] ^= 0xFF
            v["signature"] = bytes(sig).hex()
    cert = ContinuousCertifier(chain, genesis_vals)
    with pytest.raises(ReadProofError, match="certification failed"):
        CertifiedReader.verify(forged, cert)
    # trust did not advance past the forged height
    assert cert.certified_height < doc["height"]

    # 2. truncated proof chain (value height not covered)
    truncated = copy.deepcopy(doc)
    truncated["proof_commits"] = truncated["proof_commits"][:-1]
    cert2 = ContinuousCertifier(chain, genesis_vals)
    with pytest.raises(ReadProofError, match="stops at"):
        CertifiedReader.verify(truncated, cert2)

    # 3. proof for a different chain
    wrong = copy.deepcopy(doc)
    wrong["chain_id"] = "not-" + chain
    cert3 = ContinuousCertifier(chain, genesis_vals)
    with pytest.raises(ReadProofError, match="certifier follows"):
        CertifiedReader.verify(wrong, cert3)

    rej = telemetry.value("shard_cross_reads_total",
                          {"result": "rejected"})
    # verify() raises through read()'s accounting only when called via
    # read(); the direct calls above don't count — exercise one:
    reader = shard2.reader()
    reader._certifiers[chain] = ContinuousCertifier(
        chain, genesis_vals)
    orig = reads.serve_read

    def forge(node, key, since, **kw):
        d = orig(node, key, since, **kw)
        for v in d["proof_commits"][-1]["signed_header"]["commit"][
                "precommits"]:
            if v:
                sig = bytearray(bytes.fromhex(v["signature"]))
                sig[0] ^= 0xFF
                v["signature"] = bytes(sig).hex()
        return d

    reads.serve_read = forge
    try:
        with pytest.raises(ReadProofError):
            reader.read(b"forge-me")
    finally:
        reads.serve_read = orig
    rej2 = telemetry.value("shard_cross_reads_total",
                           {"result": "rejected"})
    assert (rej2 or 0) == (rej or 0) + 1


# ------------------------------------- authenticated value proofs --

@pytest.fixture
def shard2_tree(monkeypatch):
    """Two shards whose KVStore runs the authenticated state tree
    (TM_TPU_STATE_TREE=on, ISSUE 16): certified reads carry per-key
    value proofs bound to the certified app_hash."""
    monkeypatch.setenv("TM_TPU_STATE_TREE", "on")
    s = ShardSet(2, chain_prefix="ttree")
    s.start()
    try:
        assert wait_for(lambda: s.frontier() >= 2), s.heights()
        yield s
    finally:
        s.stop()


def _put_and_settle(s, key, value):
    """Write via the owning shard and wait until the value is provable
    at the stable-read version (frontier - 1, what serve_read serves)."""
    node = s.node_for_key(key)
    node.mempool.check_tx(key + b"=" + value)

    def provable():
        h = node.block_store.height()
        if h < 2:
            return False
        res = node.app_conns.query.query("", key, height=h - 1,
                                         prove=True)
        return res.code == 0 and res.value == value
    assert wait_for(provable), node.height
    return node


def test_tree_backend_certified_read_proves_value_and_absence(
        shard2_tree):
    """End-to-end chain of custody: value -> tree root -> app_hash ->
    certified commit. The reader reports proven=True, and ABSENCE is
    proven the same way — a missing key never falls back to trust."""
    key = b"proved/key"
    node = _put_and_settle(shard2_tree, key, b"certified!")
    reader = shard2_tree.reader()
    res = reader.read(key)
    assert res["proven"] is True
    assert res["value"] == b"certified!"
    assert res["value_height"] == res["height"] - 1
    # the anchor was the CERTIFIED header app hash, cached on advance
    cert = reader._certifiers[node.gen_doc.chain_id]
    assert res["value_height"] + 1 in cert.app_hashes
    res2 = reader.read(b"proved/absent-key")
    assert res2["value"] == b"" and res2["proven"] is True
    assert reader.verified_reads == 2


def test_forged_value_proofs_are_rejected(shard2_tree):
    """The ISSUE 16 forged STATE-proof matrix, stacked on PR 15's
    forged COMMIT-proof matrix: tampered leaf value, truncated path,
    sibling swap, absence-proof-for-a-present-key, wrong root. Every
    case raises ReadProofError, counts a rejected read, advances no
    verified_reads — and a later honest read still succeeds."""
    from tendermint_tpu.shard import reads

    key = b"forge/value"
    # pad the OWNING shard's tree so the proof has sibling steps to
    # tamper (a single-key tree proves with an empty path)
    owner = shard2_tree.node_for_key(key)
    for i in range(8):
        owner.mempool.check_tx(b"forge/pad%d=p" % i)
    _put_and_settle(shard2_tree, key, b"honest")
    reader = shard2_tree.reader()
    base = reader.read(key)
    assert base["proven"] and base["value"] == b"honest"

    orig = reads.serve_read

    def tampered(mutate):
        def forge(node, k, since, **kw):
            d = orig(node, k, since, **kw)
            assert d.get("value_proof"), "expected a proven read"
            mutate(d)
            return d
        return forge

    def swap_sibling(d):
        steps = d["value_proof"]["steps"]
        assert steps, "proof has no sibling steps to tamper"
        steps[0][1] = "11" * 32

    cases = {
        "tampered leaf value": lambda d: d.__setitem__(
            "value", b"forged".hex()),
        "truncated path": lambda d: d["value_proof"].__setitem__(
            "steps", d["value_proof"]["steps"][:-1]),
        "sibling swap": swap_sibling,
        "absence proof for a present key": lambda d: (
            d["value_proof"].update(present=False,
                                    other_key_hash="01" * 32,
                                    other_value_hash="02" * 32),
            d.__setitem__("value", "")),
        "wrong root (n_keys binding)": lambda d:
            d["value_proof"].update(
                n_keys=d["value_proof"]["n_keys"] + 1),
    }
    for name, mutate in cases.items():
        rej = telemetry.value("shard_cross_reads_total",
                              {"result": "rejected"}) or 0
        verified = reader.verified_reads
        reads.serve_read = tampered(mutate)
        try:
            with pytest.raises(ReadProofError, match="value proof"):
                reader.read(key)
        finally:
            reads.serve_read = orig
        assert telemetry.value("shard_cross_reads_total",
                               {"result": "rejected"}) == rej + 1, name
        assert reader.verified_reads == verified, name
    # forgeries never poisoned the certifier: honest read verifies
    res = reader.read(key)
    assert res["proven"] and res["value"] == b"honest"


def test_proof_carrying_abci_query_over_http(shard2_tree):
    """ISSUE 16 satellite: prove=True abci_query over the REAL HTTP
    front door (loop mode). The proof bytes decode client-side and
    verify against the app_hash of the NEXT height's header fetched
    via /commit — plus the tamper counterexample on the same shape."""
    from tendermint_tpu import statetree
    from tendermint_tpu.rpc.client import JSONRPCClient

    key = b"http/proved"
    node = _put_and_settle(shard2_tree, key, b"over-the-wire")
    addr = shard2_tree.serve()
    chain = shard2_tree.router.map.chain_of(key)
    c = JSONRPCClient(f"http://{addr[0]}:{addr[1]}")

    # retry: the shard commits continuously and the tree retains a
    # bounded version window, so re-pin `version` per attempt
    r = {}
    version = 0
    for _ in range(8):
        version = node.block_store.height() - 1
        r = c.call("abci_query", data=key.hex(), height=version,
                   prove=True)["response"]
        if int(r.get("code") or 0) == 0 and r.get("proof"):
            break
    assert bytes.fromhex(r["value"]) == b"over-the-wire"
    assert int(r["height"]) == version
    pf = statetree.proof_from_bytes(bytes.fromhex(r["proof"]))
    hdr = c.call("commit", height=version + 1, chain_id=chain)["header"]
    anchor = bytes.fromhex(hdr["app_hash"])
    statetree.verify(pf, key, b"over-the-wire", anchor)
    with pytest.raises(statetree.ProofError):
        statetree.verify(pf, key, b"tampered-on-the-wire", anchor)


def test_tx_search_through_front_door(shard2):
    """ISSUE 16 satellite: tx_search fans out to every shard's KV
    indexer and merges — chain-tagged records, (height, index, chain)
    order, pagination over the MERGED set, chain_id scoping."""
    import hashlib

    from tendermint_tpu.rpc.client import JSONRPCClient
    addr = shard2.serve()
    c = JSONRPCClient(f"http://{addr[0]}:{addr[1]}")

    keys = [b"srch/%d" % i for i in range(8)]
    txs = [k + b"=x" for k in keys]
    placed = {k: shard2.router.map.chain_of(k) for k in keys}
    assert len(set(placed.values())) == 2
    r = c.call("broadcast_tx_batch", txs=[t.hex() for t in txs])
    assert all(x["code"] == 0 for x in r["results"])

    # point lookup by hash WITHOUT naming the shard
    h0 = hashlib.sha256(txs[0]).hexdigest()
    assert wait_for(lambda: c.call(
        "tx_search", query=f"tx.hash='{h0}'")["total_count"] == 1)
    doc = c.call("tx_search", query=f"tx.hash='{h0}'")
    rec = doc["txs"][0]
    assert rec["chain_id"] == placed[keys[0]]
    assert bytes.fromhex(rec["tx"]) == txs[0]
    assert doc["mapping_version"] == 1

    # reserved-tag range query merges BOTH shards' results in order
    assert wait_for(lambda: c.call(
        "tx_search", query="tx.height >= 1",
        per_page=100)["total_count"] >= len(txs))
    doc = c.call("tx_search", query="tx.height >= 1", per_page=100)
    recs = doc["txs"]
    assert {x["chain_id"] for x in recs} == set(shard2.chains)
    order = [(x["height"], x["index"], x["chain_id"]) for x in recs]
    assert order == sorted(order)

    page1 = c.call("tx_search", query="tx.height >= 1", per_page=3)
    assert len(page1["txs"]) == 3
    assert page1["total_count"] == doc["total_count"]
    page2 = c.call("tx_search", query="tx.height >= 1", per_page=3,
                   page=2)
    assert page2["txs"][0] == doc["txs"][3]

    one = c.call("tx_search", query="tx.height >= 1", per_page=100,
                 chain_id=shard2.chains[0])
    assert {x["chain_id"] for x in one["txs"]} == {shard2.chains[0]}


# ------------------------------------------------------ observability --

def test_front_door_labels_and_shard_telemetry(shard2):
    addr = shard2.serve()
    from tendermint_tpu.rpc.client import JSONRPCClient
    c = JSONRPCClient(f"http://{addr[0]}:{addr[1]}")

    key = b"labelled-key"
    chain = shard2.router.map.chain_of(key)
    before = telemetry.value(
        "rpc_call_seconds",
        {"route": "broadcast_tx_sync", "chain": chain})
    r = c.call("broadcast_tx_sync", tx=(key + b"=1").hex())
    assert r["code"] == 0
    after = telemetry.value(
        "rpc_call_seconds",
        {"route": "broadcast_tx_sync", "chain": chain})
    assert after["count"] == (before["count"] if before else 0) + 1

    # chain_id params a client mints do NOT label: unknown ids fall
    # back to "" (bounded label contract)
    resolved = shard2.router.chain_of_call(
        "status", {"chain_id": "client-minted"})
    assert resolved == ""
    assert shard2.router.chain_of_call(
        "status", {"chain_id": chain}) == chain

    # per-shard height gauge updated on the commit path
    doc = c.call("shards")
    for ch in shard2.chains:
        g = telemetry.value("shard_height", {"chain": ch})
        assert g and g >= 1
    assert doc["heights"][chain] >= 1
    assert telemetry.value("shard_mapping_version") == 1

    # chain-scoped passthrough: status of a NAMED shard
    st = c.call("status", chain_id=shard2.chains[1])
    assert st["latest_block_height"] >= 1
    with pytest.raises(Exception):
        c.call("status", chain_id="no-such-chain")

    hz = c.call("healthz")
    assert hz["shards"]["n_shards"] == 2
    assert set(hz["shards"]["heights"]) == set(shard2.chains)


def test_slo_chain_attribution(monkeypatch):
    """telemetry/slo.py shard attribution: admit(chain=) flows to the
    tm_slo_stage_seconds chain label and the per-chain snapshot
    section; the chain value is server-supplied, never client-minted
    (rpc/core stamps its OWN genesis chain id)."""
    from tendermint_tpu.telemetry import slo

    monkeypatch.setenv("TM_TPU_SLO", "on")
    slo.reset()
    try:
        tx = b"slo-shard-tx"
        slo.admit(tx, chain="chain-A")
        slo.mark(tx, "checktx")
        slo.mark(tx, "commit", height=3)
        v = telemetry.value("slo_stage_seconds",
                            {"stage": "checktx", "chain": "chain-A"})
        assert v and v["count"] >= 1
        v2 = telemetry.value("slo_stage_seconds",
                             {"stage": "e2e_commit",
                              "chain": "chain-A"})
        assert v2 and v2["count"] >= 1
        snap = slo.snapshot(windows=False)
        assert snap["chains"]["chain-A"]["sampled"] == 1
        # an unattributed (gossip-arrived) tx labels chain=""
        tx2 = b"slo-plain-tx"
        slo.admit(tx2)
        slo.mark(tx2, "checktx")
        v3 = telemetry.value("slo_stage_seconds",
                             {"stage": "checktx", "chain": ""})
        assert v3 and v3["count"] >= 1
    finally:
        monkeypatch.delenv("TM_TPU_SLO")
        slo.reset()
