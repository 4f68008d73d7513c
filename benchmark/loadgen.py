"""Open-loop write load from a process of its own.

Adapted from tendermint_tpu/serving/loadgen.py (OpenLoopFleet: a
selector-driven fleet of persistent WebSocket connections, arrivals on
a clock that never looks at responses, latency counted from the DUE
time) and cut to what the net cells need, with standard-library
imports only: this process never imports JAX or the program, because
clients are other machines and do not share the nodes' interpreter.

    python -m benchmark.loadgen '<json parameters>'

It connects `conns` request connections to each RPC target and two
more, one subscribed to `tm.event = 'Tx'` and one to `tm.event =
'NewBlock'`, prints `{"ready": ...}`, and offers `key=value` writes of
`tx_bytes` bytes, keys uniform over `keyspace`, spread round-robin over
the targets, through `method` (broadcast_tx_sync: the reply is
CheckTx's). A write's commit is learned from the node it was sent to,
by the first event that names it: its own Tx event, or the NewBlock
event of the block that carries it. Its latency runs from its due time
to that event's arrival. The fleet stands for many clients behind two
subscriptions: a node's subscription holds 1,024 events and evicts the
oldest, so the Tx events of one block of more than 1,024 writes never
all arrive, where each real client would get its one. The NewBlock
event is one a block and is never evicted, so no commit goes unlearned.

Three phases. Before the window: Poisson arrivals at `rate`, so the
net warms under the cell's own load. When the parent writes
`{"open": <time.monotonic()>, "seconds": s, "drain": d}` on standard
input: exactly round(rate * s) arrivals at times drawn uniformly over
the window from the seed (a Poisson process given its count, so every
seed offers the same amount of work in another order). Then Poisson
again through the drain, so the window's last writes commit under the
same load. The drain lasts `drain` seconds, and longer while a write of
the window is neither refused nor committed, up to `drain_max` (a
height that needs a second consensus round holds writes for 4-6 s);
what is still uncommitted then is unanswered. The report goes to the
file named `out`.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import selectors
import socket
import struct
import sys
import time
from typing import Dict, List, Optional

from benchmark.chain import pad_blob, padded_tx

_WS_KEY = b"bG9hZGdlbi13cy1rZXktMDE="
EVENT_QUERIES = ("tm.event = 'Tx'", "tm.event = 'NewBlock'")


def ws_frame(data: bytes) -> bytes:
    """Client text frame, zero mask (payload rides unchanged)."""
    hdr = bytearray([0x81])
    n = len(data)
    if n < 126:
        hdr.append(0x80 | n)
    elif n < (1 << 16):
        hdr.append(0x80 | 126)
        hdr += struct.pack(">H", n)
    else:
        hdr.append(0x80 | 127)
        hdr += struct.pack(">Q", n)
    hdr += b"\x00\x00\x00\x00"
    return bytes(hdr) + data


def window_arrivals(seed: int, t_open: float, seconds: float,
                    rate: float) -> List[float]:
    """Exactly round(rate * seconds) due times in [t_open, t_open +
    seconds), sorted: the same count for every seed."""
    rng = random.Random(f"{seed}/arrivals")
    n = int(round(rate * seconds))
    return sorted(t_open + rng.random() * seconds for _ in range(n))


class _Conn:
    __slots__ = ("sock", "buf", "wbuf", "target", "alive")

    def __init__(self, sock, target: int):
        self.sock, self.target = sock, target
        self.buf = bytearray()
        self.wbuf = bytearray()
        self.alive = True


class _Write:
    __slots__ = ("due", "sent", "target", "key", "value", "phase",
                 "checked", "refused", "height", "index", "done")

    def __init__(self, due, target, key, value, phase):
        self.due, self.target, self.key, self.value = due, target, key, value
        self.phase = phase          # "warm" | "window" | "drain"
        self.sent = None
        self.checked = None         # CheckTx reply's arrival
        self.refused = None         # why the front door or CheckTx said no
        self.height = self.index = None
        self.done = None            # commit event's arrival


class Fleet:
    def __init__(self, p: dict):
        self.p = p
        self.sel = selectors.DefaultSelector()
        self.conns: List[List[_Conn]] = []      # per target, request conns
        self.subs: List[_Conn] = []
        self.rng = random.Random(f"{p['seed']}/writes")
        self.pad = pad_blob(p["seed"], "net", 8 * p["tx_bytes"] + 4096)
        self.writes: List[_Write] = []
        self.by_id: Dict[int, _Write] = {}
        self.by_hash: List[Dict[str, _Write]] = []
        self.events = 0
        self.learned = {"tx": 0, "block": 0}    # commits, by event kind
        self._next_id = 0
        self._rr = 0

    # ----------------------------------------------------------- connections

    def _connect(self, host: str, port: int, target: int) -> _Conn:
        s = socket.create_connection((host, port), timeout=10.0)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.sendall(b"GET / HTTP/1.1\r\nHost: loadgen\r\n"
                  b"Upgrade: websocket\r\nConnection: Upgrade\r\n"
                  b"Sec-WebSocket-Key: " + _WS_KEY + b"\r\n"
                  b"Sec-WebSocket-Version: 13\r\n\r\n")
        head = b""
        while b"\r\n\r\n" not in head:
            chunk = s.recv(4096)
            if not chunk:
                raise ConnectionError("closed in handshake")
            head += chunk
        if b" 101 " not in head.split(b"\r\n", 1)[0]:
            raise ConnectionError(f"no upgrade: {head[:80]!r}")
        conn = _Conn(s, target)
        conn.buf += head.partition(b"\r\n\r\n")[2]
        s.setblocking(False)
        self.sel.register(s, selectors.EVENT_READ, conn)
        return conn

    def connect(self) -> None:
        for t, (host, port) in enumerate(self.p["targets"]):
            self.conns.append([self._connect(host, port, t)
                               for _ in range(self.p["conns"])])
            self.by_hash.append({})
            for query in EVENT_QUERIES if self.p.get("subscribe", True) \
                    else ():
                sub = self._connect(host, port, t)
                self._rpc(sub, "subscribe", {"query": query})
                self.subs.append(sub)

    def close(self) -> None:
        for conn in [c for cs in self.conns for c in cs] + self.subs:
            try:
                self.sel.unregister(conn.sock)
            except (KeyError, ValueError):
                pass
            conn.sock.close()
        self.sel.close()

    # ---------------------------------------------------------------- engine

    def _rpc(self, conn: _Conn, method: str, params: dict) -> int:
        self._next_id += 1
        conn.wbuf += ws_frame(json.dumps(
            {"jsonrpc": "2.0", "id": self._next_id, "method": method,
             "params": params}).encode())
        self._flush(conn)
        return self._next_id

    def _flush(self, conn: _Conn) -> None:
        if not conn.wbuf or not conn.alive:
            return
        try:
            sent = conn.sock.send(bytes(conn.wbuf))
            del conn.wbuf[:sent]
        except (BlockingIOError, InterruptedError):
            pass
        except OSError:
            conn.alive = False

    def offer(self, due: float, phase: str) -> None:
        """One write, due at `due`, sent now."""
        p = self.p
        i = len(self.writes)
        target = self._rr % len(self.conns)
        live = self.conns[target]
        conn = live[(self._rr // len(self.conns)) % len(live)]
        self._rr += 1
        key = b"k%d" % self.rng.randrange(p["keyspace"])
        value = b"%d.%d" % (p["seed"], i)
        tx = padded_tx(key, value, self.pad, 13 * i, p["tx_bytes"])
        w = _Write(due, target, key.decode(), tx.partition(b"=")[2], phase)
        self.writes.append(w)
        self.by_hash[target][hashlib.sha256(tx).hexdigest().upper()] = w
        self.by_id[self._rpc(conn, p["method"], {"tx": tx.hex()})] = w
        w.sent = time.monotonic()

    def window_answered(self) -> bool:
        return all(w.done is not None or w.refused for w in self.writes
                   if w.phase == "window")

    def _on_frame(self, conn: _Conn, payload: bytes) -> None:
        try:
            doc = json.loads(payload)
        except ValueError:
            return
        now = time.monotonic()
        if doc.get("id") == "#event":
            self.events += 1
            res = doc.get("result") or {}
            data = res.get("data") or {}
            mine = self.by_hash[conn.target]
            if "block" in data:
                height = data["block"]["header"]["height"]
                named = [(hashlib.sha256(bytes.fromhex(t)).hexdigest()
                          .upper(), height, i) for i, t in
                         enumerate(data["block"]["data"]["txs"])]
            else:
                named = [((res.get("tags") or {}).get("tx.hash"),
                          data.get("height"), data.get("index"))]
            for tx_hash, height, index in named:
                w = mine.get(tx_hash)
                if w is not None and w.done is None:
                    w.done, w.height, w.index = now, height, index
                    self.learned["block" if "block" in data else "tx"] += 1
            return
        w = self.by_id.pop(doc.get("id"), None)
        if w is None:
            return
        w.checked = now
        err = doc.get("error")
        if err is not None:
            w.refused = f"error {err.get('code')}: {err.get('message')}"
        elif (doc.get("result") or {}).get("code", 0) != 0:
            w.refused = f"check_tx code {doc['result']['code']}"

    def _pump_conn(self, conn: _Conn) -> None:
        buf = conn.buf
        while len(buf) >= 2:
            ln = buf[1] & 0x7F
            pos = 2
            if ln == 126:
                if len(buf) < 4:
                    break
                (ln,) = struct.unpack(">H", bytes(buf[2:4]))
                pos = 4
            elif ln == 127:
                if len(buf) < 10:
                    break
                (ln,) = struct.unpack(">Q", bytes(buf[2:10]))
                pos = 10
            if len(buf) < pos + ln:
                break
            payload = bytes(buf[pos:pos + ln])
            opcode = buf[0] & 0x0F
            del buf[:pos + ln]
            if opcode == 0x8:
                conn.alive = False
                return
            if opcode in (0x9, 0xA):
                continue
            self._on_frame(conn, payload)

    def pump(self, timeout: float) -> None:
        for key, _ in self.sel.select(timeout=max(0.0, timeout)):
            conn = key.data
            if conn is None:
                continue
            try:
                data = conn.sock.recv(1 << 20)
            except (BlockingIOError, InterruptedError):
                continue
            except OSError:
                conn.alive = False
                continue
            if not data:
                conn.alive = False
                continue
            conn.buf += data
            self._pump_conn(conn)
            self._flush(conn)


def _read_open(stdin_buf: bytearray) -> Optional[dict]:
    """The parent's one line on standard input, once it is whole."""
    try:
        chunk = os.read(0, 65536)
    except (BlockingIOError, InterruptedError):
        return None
    stdin_buf += chunk
    if b"\n" not in stdin_buf:
        return None
    line, _, rest = bytes(stdin_buf).partition(b"\n")
    stdin_buf[:] = rest
    return json.loads(line)


def run(p: dict) -> dict:
    fleet = Fleet(p)
    fleet.connect()
    os.set_blocking(0, False)
    print(json.dumps({"ready": True, "targets": len(fleet.conns)}),
          flush=True)
    rate = float(p["rate"])
    arr = random.Random(f"{p['seed']}/poisson")
    stdin_buf = bytearray()
    opened: Optional[dict] = None
    window: List[float] = []
    w_i = 0
    t_open = t_close = t_end = t_max = float("inf")
    poisson_due = time.monotonic() + arr.expovariate(rate)
    give_up = time.monotonic() + float(p.get("max_wait_s", 300.0))
    try:
        while True:
            now = time.monotonic()
            if opened is None:
                if now > give_up:
                    raise TimeoutError("the parent never opened the window")
                opened = _read_open(stdin_buf)
                if opened is not None:
                    t_open = float(opened["open"])
                    t_close = t_open + float(opened["seconds"])
                    t_end = t_close + float(opened["drain"])
                    t_max = t_close + float(opened.get("drain_max",
                                                       opened["drain"]))
                    window = window_arrivals(p["seed"], t_open,
                                             float(opened["seconds"]), rate)
            if now >= t_end:
                if now >= t_max or fleet.window_answered():
                    break
                t_end = min(t_max, now + 0.25)
            # Poisson arrivals outside the window: warm before, drain after
            while poisson_due <= now and poisson_due < t_end:
                if t_open <= poisson_due < t_close:
                    poisson_due = t_close + arr.expovariate(rate)
                    continue
                fleet.offer(poisson_due,
                            "warm" if poisson_due < t_open else "drain")
                poisson_due += arr.expovariate(rate)
            # the window's own fixed list
            while w_i < len(window) and window[w_i] <= now:
                fleet.offer(window[w_i], "window")
                w_i += 1
            wake = min(poisson_due, t_end,
                       window[w_i] if w_i < len(window) else t_end)
            fleet.pump(min(max(0.0, wake - time.monotonic()), 0.02))
    finally:
        fleet.close()
    return report(fleet, opened)


def report(fleet: Fleet, opened: Optional[dict]) -> dict:
    win = [w for w in fleet.writes if w.phase == "window"]
    return {
        "opened": opened,
        "offered": {ph: sum(1 for w in fleet.writes if w.phase == ph)
                    for ph in ("warm", "window", "drain")},
        "events": fleet.events, "learned_from": fleet.learned,
        "window": [{
            "due": w.due, "target": w.target, "key": w.key,
            "value": w.value.decode("latin-1"),
            "late_ms": (w.sent - w.due) * 1000.0,
            "check_ms": None if w.checked is None
            else (w.checked - w.due) * 1000.0,
            "commit_ms": None if w.done is None
            else (w.done - w.due) * 1000.0,
            "refused": w.refused, "height": w.height, "index": w.index,
        } for w in win],
    }


def rpc_call(host: str, port: int, method: str, timeout: float = 30.0,
             **params):
    """One JSON-RPC call over HTTP with the standard library."""
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/", json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": method,
             "params": params}), {"Content-Type": "application/json"})
        doc = json.loads(conn.getresponse().read())
    finally:
        conn.close()
    if doc.get("error"):
        raise RuntimeError(f"{method}: {doc['error']}")
    return doc["result"]


def main(argv=None) -> int:
    p = json.loads((sys.argv[1:] if argv is None else argv)[0])
    out = run(p)
    tmp = p["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, p["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
