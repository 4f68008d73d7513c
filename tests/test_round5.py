"""Round-5 feature coverage: read-only WAL opens, the replay
later-ENDHEIGHT guard, batched mempool gossip, one-pass merkle tree
proofs, lazy uniform deliver results, and the bucket warmup contract."""

import os

import numpy as np
import pytest

from tendermint_tpu.storage.wal import WAL, EndHeightMessage


# ---------------------------------------------------------------- WAL

def test_readonly_wal_never_mutates_a_torn_log(tmp_path):
    """A writable open trims the torn tail; a readonly open (the replay
    CLI on a possibly-live dir) must leave the file byte-identical and
    turn save()/flush() into no-ops."""
    path = str(tmp_path / "wal")
    w = WAL(path)
    w.save({"type": "vote", "h": 1})
    w.save_end_height(1)
    w.close()
    # append a torn frame: header promising 100 payload bytes, cut
    # short mid-write (EOF truncation — the only class trim handles)
    with open(path, "ab") as f:
        f.write(b"\x01\x02\x03\x04" + (100).to_bytes(4, "big")
                + b"partial")
    before = open(path, "rb").read()

    ro = WAL(path, readonly=True)
    ro.save({"type": "vote", "h": 2})   # no-op
    ro.save_end_height(2)               # no-op
    ro.flush()
    ro.close()
    assert open(path, "rb").read() == before  # byte-identical
    # the readers still tolerate the torn head tail
    msgs = ro.all_messages()
    assert [m.msg.get("type") for m in msgs] == ["endheight", "vote",
                                                 "endheight"]

    # a writable reopen trims it (existing behavior, still intact)
    W2 = WAL(path)
    W2.close()
    assert len(open(path, "rb").read()) < len(before)


def test_replay_rejects_endheight_past_state_height(tmp_path):
    """wal_tail_for must refuse a tail that spans FURTHER committed
    heights (state store behind WAL) instead of double-replaying them
    — the reference's catchupReplay errors the same way."""
    from tendermint_tpu.consensus.replay import wal_tail_for

    path = str(tmp_path / "wal")
    w = WAL(path)
    w.save_end_height(3)
    w.save({"type": "vote", "h": 4})
    w.save_end_height(4)          # state store lost height 4
    w.close()
    with pytest.raises(ValueError, match="ENDHEIGHT 4"):
        wal_tail_for(w, 3)
    # a clean tail (no later markers) still replays
    assert wal_tail_for(w, 4) == []


# ------------------------------------------------------- mempool gossip

class _FakePeer:
    def __init__(self):
        self.id = "fake-peer"
        self.running = True
        self.sent = []

    def send(self, ch, payload):
        self.sent.append(payload)
        return True

    def get(self, key):
        return None


def test_batched_tx_gossip_message_roundtrip():
    """A 'txs' batch message admits every tx; a malformed batch stops
    the peer like any protocol violation."""
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.mempool.reactor import MempoolReactor
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.types import encoding

    conns = AppConns(local_client_creator(KVStoreApp()))
    mp = Mempool(conns.mempool)
    r = MempoolReactor(mp, broadcast=False)
    peer = _FakePeer()
    r.receive(0x30, peer, encoding.cdumps(
        {"type": "txs", "txs": [b"a=1".hex(), b"b=2".hex()]}))
    assert mp.size() == 2
    # single-tx form still works
    r.receive(0x30, peer, encoding.cdumps(
        {"type": "tx", "tx": b"c=3".hex()}))
    assert mp.size() == 3

    stopped = []

    class _Switch:
        def stop_peer_for_error(self, p, e):
            stopped.append((p.id, str(e)))

    r.switch = _Switch()
    r.receive(0x30, peer, encoding.cdumps(
        {"type": "txs", "txs": "deadbeef"}))  # not a list
    assert stopped and "batch" in stopped[0][1]
    assert mp.size() == 3


def test_broadcast_routine_batches_backlog():
    """With a backlog in the clist, one send carries many txs."""
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.mempool.reactor import MempoolReactor
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.proxy import AppConns, local_client_creator
    from tendermint_tpu.types import encoding
    import threading

    conns = AppConns(local_client_creator(KVStoreApp()))
    mp = Mempool(conns.mempool)
    for i in range(40):
        mp.check_tx(b"k%d=v" % i)
    r = MempoolReactor(mp, broadcast=False)
    peer = _FakePeer()
    t = threading.Thread(target=r._broadcast_tx_routine, args=(peer,),
                         daemon=True)
    t.start()
    deadline = 5.0
    import time
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        got = sum(
            len(m.get("txs", [m.get("tx")]))
            for m in (encoding.cloads(p) for p in list(peer.sent)))
        if got >= 40:
            break
        time.sleep(0.05)
    r.stop()
    peer.running = False
    t.join(timeout=2)
    msgs = [encoding.cloads(p) for p in peer.sent]
    total = sum(len(m.get("txs", [m.get("tx")])) for m in msgs)
    assert total == 40
    # the backlog must have coalesced: far fewer messages than txs
    assert len(msgs) <= 4, f"{len(msgs)} messages for 40 txs"


# ------------------------------------------------------------- merkle

def test_tree_proofs_host_matches_per_item_proofs():
    from tendermint_tpu.ops import merkle
    rng = np.random.RandomState(9)
    for n in (1, 2, 5, 33, 400):
        items = [rng.bytes(rng.randint(0, 80)) for _ in range(n)]
        root, proofs = merkle.tree_proofs_host(items)
        assert len(proofs) == n
        for i in range(n):
            r2, aunts = merkle.proof_host(items, i)
            assert r2 == root
            assert aunts == proofs[i]
            assert merkle.verify_proof_host(root, n, i, items[i],
                                            proofs[i])
        # tamper: a wrong item fails against its own proof
        if n > 1:
            assert not merkle.verify_proof_host(root, n, 0, b"evil",
                                                proofs[0])


# ------------------------------------------- lazy uniform results

def test_uniform_results_lazy_keys_roundtrip():
    from tendermint_tpu.abci.types import UniformDeliverResults

    packed = b"".join(len(k).to_bytes(4, "little") + k
                      for k in (b"k1", b"key2", b""))
    r = UniformDeliverResults(None, packed=packed, n=3)
    assert len(r) == 3
    assert r._keys is None           # nothing materialized yet
    o = r.to_compact_obj()           # persists from the blob
    assert r._keys is None
    r2 = UniformDeliverResults.from_compact_obj(o)
    assert r2._keys is None          # load path stays lazy too
    assert r2[1].tags["app.key"] == "key2"
    assert r2.keys == [b"k1", b"key2", b""]


