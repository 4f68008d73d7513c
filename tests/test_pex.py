"""AddrBook + PEX reactor tests (models p2p/pex/addrbook_test.go,
pex_reactor_test.go)."""

import time

import pytest

from tendermint_tpu.p2p import NetAddress, pubkey_to_id
from tendermint_tpu.p2p.fuzz import FuzzConfig, FuzzedLink
from tendermint_tpu.p2p.pex import PEX_CHANNEL, AddrBook, PEXReactor
from tendermint_tpu.p2p.test_util import connect_switches, make_switch


def ra(i, j=0, port=26656, with_id=True):
    """Routable address i.j in distinct /16 groups."""
    id_ = pubkey_to_id(bytes([i, j]) + bytes(30)) if with_id else ""
    return NetAddress(f"8.{i}.{j}.1", port, id_)


def test_addrbook_add_pick_markgood():
    book = AddrBook(key=b"k" * 24)
    src = ra(0)
    for i in range(1, 20):
        assert book.add_address(ra(i), src)
    assert book.size() == 19
    a = book.pick_address()
    assert a is not None and book.has(a)
    # promote: moves to old bucket, re-add rejected
    book.mark_good(ra(1))
    assert not book.add_address(ra(1), src)
    # old addrs still picked with bias toward old
    picked_old = any(book.pick_address(new_bias_pct=0) == ra(1)
                     for _ in range(100))
    assert picked_old


def test_addrbook_rejects_unroutable_when_strict():
    book = AddrBook(strict=True, key=b"k" * 24)
    assert not book.add_address(
        NetAddress("127.0.0.1", 26656, ""), ra(0))
    assert not book.add_address(
        NetAddress("10.1.2.3", 26656, ""), ra(0))
    loose = AddrBook(strict=False, key=b"k" * 24)
    assert loose.add_address(NetAddress("127.0.0.1", 26656, ""), ra(0))


def test_addrbook_own_address_excluded():
    book = AddrBook(key=b"k" * 24)
    me = ra(5)
    book.add_our_address(me)
    assert not book.add_address(me, ra(0))


def test_addrbook_selection_bounds():
    book = AddrBook(key=b"k" * 24)
    assert book.get_selection() == []
    src = ra(0)
    for i in range(1, 50):
        book.add_address(ra(i), src)
    sel = book.get_selection()
    assert 1 <= len(sel) <= 250
    assert all(book.has(a) for a in sel)


def test_addrbook_eviction_on_full_bucket():
    book = AddrBook(key=b"k" * 24)
    src = ra(0)
    # same /16 group + same src: all land in one new bucket (64 cap)
    added = 0
    for j in range(1, 200):
        if book.add_address(NetAddress("8.1.0.%d" % (j % 250 + 1),
                                       20000 + j,
                                       pubkey_to_id(bytes([7, j % 256]) +
                                                    bytes(30))), src):
            added += 1
    assert added >= 64  # kept absorbing via eviction
    assert book.size() <= added


def test_addrbook_persistence(tmp_path):
    path = str(tmp_path / "addrbook.json")
    book = AddrBook(path=path, key=b"k" * 24)
    src = ra(0)
    for i in range(1, 10):
        book.add_address(ra(i), src)
    book.mark_good(ra(3))
    book.save()
    book2 = AddrBook(path=path)
    assert book2.size() == book.size()
    assert book2.has(ra(3))
    assert book2._addrs[book2._addr_key(ra(3))].is_old()


def test_pex_request_response_fills_book():
    book1 = AddrBook(strict=False, key=b"a" * 24)
    book2 = AddrBook(strict=False, key=b"b" * 24)
    for i in range(1, 30):
        book2.add_address(ra(i), ra(0))
    r1 = PEXReactor(book1, ensure_peers_period=1000)
    r2 = PEXReactor(book2, ensure_peers_period=1000)
    sw1 = make_switch(seed=b"\x01" * 32)
    sw2 = make_switch(seed=b"\x02" * 32)
    sw1.add_reactor("pex", r1)
    sw2.add_reactor("pex", r2)
    p1, p2 = connect_switches(sw1, sw2)
    # add_peer auto-requested addresses (book empty); they flow back
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and book1.size() == 0:
        time.sleep(0.02)
    assert book1.size() > 0
    sw1.stop(); sw2.stop()


def test_pex_first_request_is_served_on_a_host_that_just_booted(monkeypatch):
    """time.monotonic() counts from boot: a peer's FIRST request, on a
    host up for less than period/3, is no flood; its second is."""
    import types

    from tendermint_tpu.p2p.pex import pex_reactor

    class Sw:
        stopped = []

        def stop_peer_for_error(self, peer, err):
            self.stopped.append(str(err))

    class Peer:
        id, outbound, sent = "p", False, []

        def try_send_obj(self, ch, obj):
            self.sent.append(obj["type"])

    monkeypatch.setattr(pex_reactor, "time",
                        types.SimpleNamespace(monotonic=lambda: 5.0))
    r = PEXReactor(AddrBook(strict=False, key=b"a" * 24),
                   ensure_peers_period=1000)
    r.switch, peer = Sw(), Peer()
    request = b'{"type":"pex_request"}'
    r.receive(PEX_CHANNEL, peer, request)
    assert (peer.sent, r.switch.stopped) == (["pex_addrs"], [])
    r.receive(PEX_CHANNEL, peer, request)
    assert (peer.sent, r.switch.stopped) == (["pex_addrs"],
                                             ["pex request flood"])


def test_pex_unsolicited_addrs_disconnects_peer():
    book = AddrBook(strict=False, key=b"a" * 24)
    r1 = PEXReactor(book, ensure_peers_period=1000)
    sw1 = make_switch(seed=b"\x01" * 32)
    sw2 = make_switch(seed=b"\x02" * 32)
    sw1.add_reactor("pex", r1)
    sw2.add_reactor("pex", PEXReactor(
        AddrBook(strict=False, key=b"b" * 24), ensure_peers_period=1000))
    p1, p2 = connect_switches(sw1, sw2)
    # sw2 pushes addrs sw1 never asked for
    from tendermint_tpu.types import encoding
    p2.send(PEX_CHANNEL, encoding.cdumps(
        {"type": "pex_addrs", "addrs": [ra(1).to_obj()]}))
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and sw1.peers.size() > 0:
        time.sleep(0.02)
    assert sw1.peers.size() == 0
    sw1.stop(); sw2.stop()


def test_fuzzed_link_drops_but_mconn_survives():
    """Reactor messages still arrive (eventually) across a lossy link in
    delay mode; drop mode drops whole frames without crashing."""
    import socket
    import threading
    from tendermint_tpu.p2p import ChannelDescriptor, MConnection
    from tendermint_tpu.p2p.conn.mconn import PlainFramedConn

    s1, s2 = socket.socketpair()
    recv2 = []
    errs = []
    fuzz = FuzzedLink(PlainFramedConn(s1),
                      FuzzConfig(mode="delay", prob_sleep=0.5,
                                 max_delay_s=0.01, seed=7))
    m1 = MConnection(fuzz, [ChannelDescriptor(1)],
                     on_receive=lambda ch, m: None,
                     on_error=errs.append)
    m2 = MConnection(PlainFramedConn(s2), [ChannelDescriptor(1)],
                     on_receive=lambda ch, m: recv2.append(m),
                     on_error=errs.append)
    m1.start(); m2.start()
    for i in range(20):
        m1.send(1, b"msg%d" % i)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and len(recv2) < 20:
        time.sleep(0.02)
    assert len(recv2) == 20
    m1.stop(); m2.stop()


def test_fuzzed_link_drop_mode_loses_frames():
    class FakeLink:
        def __init__(self):
            self.wrote = []

        def write(self, b):
            self.wrote.append(b)
            return len(b)

        def close(self):
            pass

    fake = FakeLink()
    fuzz = FuzzedLink(fake, FuzzConfig(mode="drop", prob_drop_rw=0.5,
                                       seed=42))
    for i in range(100):
        fuzz.write(b"x")
    assert 10 < len(fake.wrote) < 90  # some dropped, some delivered
