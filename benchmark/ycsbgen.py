"""YCSB workload A's clients, from a process of its own: benchmark/
loadgen.py's fleet, arrivals, phases and drain (nothing of that is
written again here) with its one kind of operation replaced by two.

    python -m benchmark.ycsbgen '<json parameters>'

An operation is a proven read with probability `read_share`, else an
update; the kind and the key of each arrival are drawn from the seed,
the key by benchmark/ycsb.py's one generator for both (Zipfian rank,
scrambled). An update is a write as loadgen's: `key=value` of
`record_bytes` fresh seeded bytes through `method`, its commit learned
from the first Tx or NewBlock event that names it. A read is
`abci_query path=/store prove=true height=0` on the connection the
arrival falls on; it is answered when the reply arrives, and the whole
reply (the version it names, the value, the proof) goes into the report
for the driver to check. Standard library only: clients are other
machines.
"""

from __future__ import annotations

import hashlib
import json
import random
import sys
import time

from benchmark import loadgen, ycsb


class _Op(loadgen._Write):
    __slots__ = ("kind", "item", "i", "reply")


class YcsbFleet(loadgen.Fleet):
    def __init__(self, p: dict):
        super().__init__(dict(p, tx_bytes=int(p["record_bytes"])))
        self.keys = ycsb.KeyChooser(p["seed"], int(p["recordcount"]),
                                    float(p["theta"]))
        self.values = ycsb.Values(p["seed"], int(p["record_bytes"]))
        self.kinds = random.Random(f"{p['seed']}/ycsb/kinds")
        self.read_share = float(p["read_share"])
        self.reads_by_id = {}

    def offer(self, due: float, phase: str) -> None:
        """One operation, due at `due`, sent now."""
        i = len(self.writes)
        target = self._rr % len(self.conns)
        live = self.conns[target]
        conn = live[(self._rr // len(self.conns)) % len(live)]
        self._rr += 1
        read = self.kinds.random() < self.read_share
        item = self.keys.next_item()
        key = ycsb.key_of(item)
        op = _Op(due, target, key.decode(), None, phase)
        op.kind, op.item, op.i, op.reply = \
            "read" if read else "update", item, i, None
        self.writes.append(op)
        if read:
            self.reads_by_id[self._rpc(conn, "abci_query", {
                "path": "/store", "data": key.hex(), "height": 0,
                "prove": True})] = op
        else:
            tx = key + b"=" + self.values.update(i)
            self.by_hash[target][
                hashlib.sha256(tx).hexdigest().upper()] = op
            self.by_id[self._rpc(conn, self.p["method"],
                                 {"tx": tx.hex()})] = op
        op.sent = time.monotonic()

    def _on_frame(self, conn, payload: bytes) -> None:
        if self.reads_by_id and b'"#event"' not in payload[:64]:
            try:
                doc = json.loads(payload)
            except ValueError:
                return
            op = self.reads_by_id.pop(doc.get("id"), None)
            if op is not None:
                op.checked = op.done = time.monotonic()
                err = doc.get("error")
                resp = (doc.get("result") or {}).get("response") or {}
                if err is not None:
                    op.done = None
                    op.refused = f"error {err.get('code')}: " \
                                 f"{err.get('message')}"
                elif resp.get("code", 0) != 0:
                    op.done = None
                    op.refused = f"query code {resp['code']}: " \
                                 f"{resp.get('log')}"
                else:
                    op.reply = resp
                return
        super()._on_frame(conn, payload)


def report(fleet: YcsbFleet, opened) -> dict:
    win = [op for op in fleet.writes if op.phase == "window"]
    return {
        "opened": opened,
        "offered": {ph: sum(1 for op in fleet.writes if op.phase == ph)
                    for ph in ("warm", "window", "drain")},
        "events": fleet.events, "learned_from": fleet.learned,
        "window": [{
            "kind": op.kind, "item": op.item, "i": op.i, "key": op.key,
            "due": op.due, "target": op.target,
            "late_ms": (op.sent - op.due) * 1000.0,
            "check_ms": None if op.checked is None
            else (op.checked - op.due) * 1000.0,
            # an update's commit, a read's reply
            "commit_ms": None if op.done is None
            else (op.done - op.due) * 1000.0,
            "refused": op.refused, "height": op.height, "index": op.index,
            "reply": op.reply,
        } for op in win],
    }


def main(argv=None) -> int:
    # loadgen.run builds `Fleet` and ends with `report`: the arrivals,
    # the three phases and the drain are its own, the fleet and what
    # it reports are this file's
    loadgen.Fleet, loadgen.report = YcsbFleet, report
    return loadgen.main(sys.argv[1:] if argv is None else argv)


if __name__ == "__main__":
    sys.exit(main())
