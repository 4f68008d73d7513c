"""Mempool + CList tests (models mempool/mempool_test.go + clist tests)."""

import threading
import time

import pytest

from tendermint_tpu.abci.apps import CounterApp
from tendermint_tpu.abci.proxy import AppConns, local_client_creator
from tendermint_tpu.mempool import CList, Mempool, TxAlreadyInCache, TxCache


def make_mempool(app=None):
    app = app or CounterApp(serial=False)
    conns = AppConns(local_client_creator(app))
    return Mempool(conns.mempool), app


# ------------------------------------------------------------------- CList

def test_clist_push_iterate_remove():
    cl = CList()
    els = [cl.push_back(i) for i in range(5)]
    assert len(cl) == 5
    assert [e.value for e in cl] == [0, 1, 2, 3, 4]
    cl.remove(els[2])
    assert [e.value for e in cl] == [0, 1, 3, 4]
    # removed element still reaches the live suffix
    assert els[2].next().value == 3
    cl.remove(els[0])
    assert cl.front().value == 1


def test_clist_next_wait_wakes_on_push():
    cl = CList()
    el = cl.push_back("a")
    got = []

    def waiter():
        got.append(el.next_wait(timeout=5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    cl.push_back("b")
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert got[0].value == "b"


def test_clist_front_wait_timeout():
    cl = CList()
    t0 = time.monotonic()
    assert cl.front_wait(timeout=0.05) is None
    assert time.monotonic() - t0 >= 0.04


# ------------------------------------------------------------------ TxCache

def test_tx_cache_dedup_and_eviction():
    c = TxCache(size=2)
    assert c.push(b"a") and not c.push(b"a")
    assert c.push(b"b")
    assert c.push(b"c")        # evicts a (FIFO)
    assert c.push(b"a")        # a admitted again
    c.remove(b"c")
    assert c.push(b"c")


# ------------------------------------------------------------------ Mempool

def test_checktx_reap_order_and_dedup():
    mp, _ = make_mempool()
    for i in range(10):
        res = mp.check_tx(bytes([i]))
        assert res.ok
    assert mp.size() == 10
    assert mp.reap(4) == [bytes([i]) for i in range(4)]
    assert mp.reap(-1) == [bytes([i]) for i in range(10)]
    with pytest.raises(TxAlreadyInCache):
        mp.check_tx(bytes([3]))


def test_invalid_tx_not_queued_not_cached():
    # serial counter app rejects txs below its count (abci/apps/counter.py)
    app = CounterApp(serial=True)
    mp, _ = make_mempool(app)
    for i in range(3):
        app.deliver_tx(i.to_bytes(8, "big"))  # count -> 3
    bad = (1).to_bytes(8, "big")
    res = mp.check_tx(bad)
    assert not res.ok and mp.size() == 0
    # rejected txs leave the cache so a later resubmit re-checks
    res = mp.check_tx(bad)
    assert not res.ok


def test_update_removes_committed_and_keeps_cache():
    mp, _ = make_mempool()
    txs = [bytes([i]) for i in range(6)]
    for tx in txs:
        mp.check_tx(tx)
    mp.lock()
    mp.update(1, txs[:3])
    mp.unlock()
    assert mp.reap(-1) == txs[3:]
    # committed txs stay cached: resubmit is a dup
    with pytest.raises(TxAlreadyInCache):
        mp.check_tx(txs[0])


def test_a_tx_first_seen_in_its_block_is_a_dup_when_gossip_brings_it():
    mp, _ = make_mempool()
    late = b"\x00\x07"
    # another node proposed it: this one never had it pending
    mp.lock()
    mp.update(1, [late])
    mp.unlock()
    with pytest.raises(TxAlreadyInCache):
        mp.check_tx(late)
    assert mp.size() == 0 and mp.reap(-1) == []


def test_update_recheck_drops_newly_invalid():
    app = CounterApp(serial=True)
    conns = AppConns(local_client_creator(app))
    mp = Mempool(conns.mempool)
    good = [(i).to_bytes(8, "big") for i in range(4)]
    for tx in good:
        assert mp.check_tx(tx).ok
    # app advanced to count 3 out-of-band, but only [0,1] were committed:
    # the recheck after update must drop the now-stale tx 2, keep tx 3
    for tx in good[:3]:
        app.deliver_tx(tx)
    mp.update(1, good[:2])
    assert mp.reap(-1) == good[3:]


def test_txs_available_fires_once_per_height():
    mp, _ = make_mempool()
    fired = []
    mp.txs_available_hook = lambda: fired.append(mp.height)
    mp.check_tx(b"x")
    mp.check_tx(b"y")
    assert fired == [0]          # once, not per tx
    mp.update(1, [b"x"])
    assert fired == [0, 1]       # txs remain -> re-notify at new height


def test_mempool_full_raises():
    class Cfg:
        size = 3
        recheck = True
        cache_size = 100
    app = CounterApp(serial=False)
    conns = AppConns(local_client_creator(app))
    mp = Mempool(conns.mempool, config=Cfg())
    for i in range(3):
        mp.check_tx(bytes([i]))
    from tendermint_tpu.mempool.mempool import MempoolFull
    with pytest.raises(MempoolFull):
        mp.check_tx(b"overflow")


def test_wal_replay_restores_pending_txs(tmp_path):
    wal_dir = str(tmp_path / "mwal")
    app = CounterApp(serial=False)
    conns = AppConns(local_client_creator(app))
    mp = Mempool(conns.mempool, wal_dir=wal_dir)
    txs = [b"\n\x00weird" + bytes([i]) for i in range(5)]  # embedded newlines
    for tx in txs:
        mp.check_tx(tx)
    mp.close()
    # crash + restart: a fresh mempool replays the WAL through CheckTx
    mp2 = Mempool(AppConns(local_client_creator(CounterApp())).mempool,
                  wal_dir=wal_dir)
    assert mp2.reap(-1) == txs


def test_wal_committed_txs_never_replay(tmp_path):
    wal_dir = str(tmp_path / "mwal")
    conns = AppConns(local_client_creator(CounterApp()))
    mp = Mempool(conns.mempool, wal_dir=wal_dir)
    txs = [bytes([i]) for i in range(4)]
    for tx in txs:
        mp.check_tx(tx)
    mp.update(1, txs[:2])  # commit 0,1 -> WAL rewritten to pending only
    mp.close()
    mp2 = Mempool(AppConns(local_client_creator(CounterApp())).mempool,
                  wal_dir=wal_dir)
    assert mp2.reap(-1) == txs[2:]


def test_pending_tx_resubmit_after_cache_eviction_is_dup():
    class Cfg:
        size = 1000
        recheck = True
        cache_size = 2  # tiny: pending txs outlive their cache entries
    conns = AppConns(local_client_creator(CounterApp()))
    mp = Mempool(conns.mempool, config=Cfg())
    mp.check_tx(b"T")
    mp.check_tx(b"a")
    mp.check_tx(b"b")  # evicts T from cache; T still pending
    with pytest.raises(TxAlreadyInCache):
        mp.check_tx(b"T")
    assert mp.reap(-1) == [b"T", b"a", b"b"]  # no duplicate element


def test_wal_replay_drops_torn_tail(tmp_path):
    import os
    wal_dir = str(tmp_path / "mwal")
    conns = AppConns(local_client_creator(CounterApp()))
    mp = Mempool(conns.mempool, wal_dir=wal_dir)
    mp.check_tx(b"complete")
    mp.close()
    path = os.path.join(wal_dir, "wal")
    with open(path, "ab") as f:
        f.write(b"\x00\x00\x00\xffhalf-a-record")  # truncated frame
    mp2 = Mempool(AppConns(local_client_creator(CounterApp())).mempool,
                  wal_dir=wal_dir)
    assert mp2.reap(-1) == [b"complete"]


def test_concurrent_checktx_threadsafe():
    mp, _ = make_mempool()
    errs = []

    def feed(base):
        try:
            for i in range(50):
                mp.check_tx(base + i.to_bytes(2, "big"))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=feed, args=(bytes([t]),))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert mp.size() == 200
