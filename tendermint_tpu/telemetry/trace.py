"""Lightweight tracing — a bounded in-memory event ring dumpable as
Chrome-trace JSON (chrome://tracing / Perfetto "traceEvents" format).

The one span recorder inside the program: the verifier, the certifier,
the sync window engine, apply and the consensus state machine record
here, under the names of the closed catalogue `SPANS`. Everything is
gated on the same process-wide enabled flag as the metrics registry, so
`TM_TPU_TELEMETRY=off` makes a span a single flag check.

A span's event carries `id` (a process-wide sequence), `parent` (the
innermost span open on the same thread when it started), `cause` (the
id of a span on another thread, handed over by the caller) and `req`
(the request it belongs to; a span given none takes its parent's).
Where JAX is loaded a span also enters
`jax.profiler.TraceAnnotation("tm:" + name)`, so it lands in the
profiler's own trace beside the device planes, whoever started the
profiler; outside a profiler session that is one atomic check.

Timestamps are perf_counter-relative microseconds (Chrome trace's native
unit); `pid` is the real process id so multi-node testnet dumps can be
merged by concatenating traceEvents.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from array import array
from typing import List, Tuple

from tendermint_tpu.telemetry.registry import _on_enabled, _state

# Default ring capacity: one consensus step is ~5 events, so this holds
# some ten thousand heights of timeline before the oldest roll off; and
# a traced fast-sync writes 9 events a block, so it holds a 45 s window
# of ten passes of 1,024 blocks (90,000 events) with none lost. The
# columns are allocated once: 88 bytes a slot, 23 MB.
DEFAULT_CAPACITY = 262144

# Ring overflow accounting, shared with the causal span ring
# (telemetry/causal.py): long soaks stay bounded BY DESIGN, and the
# counter is how a dump consumer learns its window was truncated.
from tendermint_tpu.telemetry.registry import REGISTRY as _REGISTRY

_m_dropped = _REGISTRY.counter(
    "trace_events_dropped_total",
    "Trace ring events displaced by the capacity cap "
    "(Chrome tracer + causal span ring)", ())


def note_dropped(n: int = 1) -> None:
    _m_dropped.inc(n)


# The closed catalogue, name -> layer (PERF.md section 3 has the
# layers): every literal name at a span/complete/instant call site must
# be declared here (analysis/checkers/metrics.py lints the call sites).
# Never one span per signature, transaction, vote or part.
SPANS = {
    "verify.dispatch": "verifier",      # _verify_async_direct, whole
    "verify.prep": "verifier",          # SHA-512 + mod L on the host
    "verify.predecomp": "verifier",     # cache rows for one chunk
    "verify.enqueue": "device kernels",  # transfers + the jitted call
    "verify.fetch": "verifier",         # blocking fetch of the verdicts
    "verify.inflight": "device",        # first enqueue -> end of fetch
    # one commit, one caller that waits (ValidatorSet.verify_commit_async
    # and its finisher; req = the commit's height)
    "commit.collect": "verifier",
    "commit.wait": "verifier",          # verify.fetch nests in it
    "commit.check": "verifier",
    "lite.collect": "verifier",
    # its two passes over a window, one event each (not one a header)
    "lite.headers": "verifier",         # validate_basic, valset hash
    "lite.votes": "verifier",           # the commits' columns
    "lite.wait": "verifier",
    "lite.check": "verifier",
    # one event each a window, as lite.headers: the validator sets the
    # window's headers hand over, hashed (the first part of
    # lite.headers; `sets` = distinct set objects), and a follower's
    # boundaries judged by the adjacent-height rule with the switch of
    # trust (last in lite.check; ContinuousCertifier.advance_many alone)
    "lite.sethash": "verifier",
    "lite.transition": "verifier",
    "sync.collect": "sync window engine",
    "sync.parts": "sync window engine",
    "sync.wait": "sync window engine",
    "sync.apply": "sync window engine",
    "sync.store": "sync window engine",
    # one event per block for which its window had no lanes (a commit
    # no set would take, or a block id rebuilt at another part size):
    # the synchronous verify_commit under the live set (commit.* nest
    # in it; req = the height). A set that moves, grows or shrinks
    # fires none: its windows' verdicts are judged by their keys under
    # the set in force
    "sync.reverify": "verifier",
    # that judge, one event per block that came with lanes
    # (ValidatorSet.check_commit_lanes in _apply_window: the keys
    # compared, the lanes under another key verified again, the live
    # stake tallied; req = the height, `again` = lanes verified again)
    "sync.judge": "verifier",
    # one event per applied block that brought a key into force under
    # an address the set before did not hold (BlockchainReactor._repair:
    # the newcomers' lanes in the blocks collected and not yet applied,
    # verified under that key in one verifier call, verify.* nest in
    # it; req = the height that brought the key, `lanes` = lanes
    # verified, `blocks` = blocks they lie in). What it missed shows as
    # `again` in those blocks' sync.judge
    "sync.repair": "verifier",
    "wire.decode_block": "sync window engine",
    "apply.validate": "apply and Merkle",   # incl. the data hash again
    "apply.exec": "apply and Merkle",
    "apply.commit": "apply and Merkle",
    "apply.save": "apply and Merkle",
    # update_state, one event a block: EndBlock's validator updates
    # (`changed` of them) through update_with_changes, the proposer
    # rotation over every power, the next State
    "apply.update": "apply and Merkle",
    # the authenticated state tree and the read path: one event per
    # StateTree.commit (nests in apply.commit; req = the version), one
    # per bulk load of a store (InitChain's records, a snapshot
    # restore), one per abci_query that reaches the KVStore (req = the
    # version served)
    "tree.commit": "state tree and read path",
    "tree.load": "state tree and read path",
    "app.query": "state tree and read path",
    # one interval per consensus step (consensus/state._new_step), one
    # instant per committed block and per timeout that moved the state
    "cs:NEW_HEIGHT": "gossip and consensus rounds",
    "cs:NEW_ROUND": "gossip and consensus rounds",
    "cs:PROPOSE": "gossip and consensus rounds",
    "cs:PREVOTE": "gossip and consensus rounds",
    "cs:PREVOTE_WAIT": "gossip and consensus rounds",
    "cs:PRECOMMIT": "gossip and consensus rounds",
    "cs:PRECOMMIT_WAIT": "gossip and consensus rounds",
    "cs:COMMIT": "gossip and consensus rounds",
    "cs:finalize_commit": "gossip and consensus rounds",
    "cs:timeout": "gossip and consensus rounds",
    # one event per VoteSet.add_vote / add_votes_batch call with its
    # verify (a gossip message's votes, never one per vote); req = the
    # height, `sigs` = signatures the call sent to the verifier
    "cs:vote_ingest": "gossip and consensus rounds",
    # what PROPOSE and COMMIT are made of (consensus/state._cspan and
    # _cwait; req = the height, args `round` and `node`): at most one
    # event of each per node, height and round, never one per part.
    # The proposer's two run before its PROPOSE step opens
    # (_enter_propose changes the step last); a receiver's two waits
    # add up to its PROPOSE step, and a step that ends in its timeout
    # records neither
    "cs:propose.build": "gossip and consensus rounds",   # reap .. signed
    "cs:propose.send": "gossip and consensus rounds",    # own queue, peers
    "cs:propose.await_proposal": "gossip and consensus rounds",
    "cs:propose.await_block": "gossip and consensus rounds",
    "cs:commit.validate": "gossip and consensus rounds",
    "cs:commit.persist": "gossip and consensus rounds",  # flush, WAL fsync
    # why a round is lost: one instant per nil vote the node signs
    # (args `type`, `round`, `node`, `why`), one per episode of a queue
    # over 80% full (telemetry/queues._fire; args `queue`, `depth`)
    "cs:nil_vote": "gossip and consensus rounds",
    "queue.saturated": "gossip and consensus rounds",
    # one event per collection of the cyclic collector that took
    # GC_EVENT_MIN_S or more, on the thread it stopped (args `gen`,
    # `collected`); every collection moves tm_gc_*_total
    "gc.collect": "host runtime",
}

ANNOTATION_PREFIX = "tm:"   # a span's name in a profiler trace

_ids = itertools.count(1)       # next() is atomic under the GIL
# .stack: the spans open on this thread; .gc_started: when the
# collection it is in began
_local = threading.local()


def _open_spans() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _NullSpan:
    """What span() hands out when telemetry is off: nothing is taken,
    nothing is kept."""
    __slots__ = ()
    id = 0
    req = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One timed block. `id` is there from the start, so a caller can
    hand it to another thread as that thread's `cause`."""
    __slots__ = ("_tracer", "name", "id", "parent", "cause", "req",
                 "args", "_t0", "_ann")

    def __init__(self, tracer, name, req, cause, args):
        self._tracer, self.name, self.args = tracer, name, args
        self.id, self.parent = next(_ids), 0
        self.req, self.cause = req, cause
        self._ann = None

    def __enter__(self):
        stack = _open_spans()
        if stack:
            outer = stack[-1]
            self.parent = outer.id
            if self.req is None:
                self.req = outer.req
        stack.append(self)
        jax = sys.modules.get("jax")    # never imported from here
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _open_spans().pop()
        self._tracer._record_span(self.name, self._t0, t1, self.id,
                                  self.parent, self.cause, self.req,
                                  self.args)
        return False


# what a slot's int columns hold, in this order
_TID, _ID, _PARENT, _CAUSE, _REQ, _ARG = range(6)
_INTS = 6
_NO_REQ = -(1 << 63)        # a slot whose event has no int `req`
_NAMES = {name: name for name in SPANS}     # a name -> the catalogue's own


def _fits(value) -> bool:
    """An int (no bool) that an int column holds."""
    return type(value) is int and _NO_REQ < value < -_NO_REQ


class Tracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        # explicit cap + drop accounting: a week-long soak whose ring
        # wrapped looks exactly like a quiet node unless the drops are
        # counted
        n = self._capacity = max(1, int(capacity))
        # The ring is columns allocated once, not an object per event.
        # What a span records must not sit in the heap among the
        # objects of the work it times: with each event kept as a dict,
        # a traced fast-sync read 1.1 ms a block more apply and 1 ms a
        # block less decode than the same code recording nothing
        # (PERF.md, PR 24). A lone int arg (`bytes=n`) goes into the
        # columns too, under its key; only other `args`, and a `req`
        # that is no int, are kept as the caller's objects, by slot.
        self._name = [None] * n                 #: guarded_by _lock
        self._ts = array("d", bytes(8 * n))     # start, us since _t0
        self._dur = array("d", bytes(8 * n))    # us; below 0: an instant
        self._ints = array("q", bytes(8 * _INTS * n))
        self._argkey = [None] * n   # a lone int arg's key; value: _ARG
        self._objs = {}             # slot -> (req, args) where any
        self._written = 0           # since clear(); next slot: % n
        self.dropped = 0                        #: guarded_by _lock
        # when each displaced event ended (us, like `ts`): what lets a
        # reader tell a window that lost events from one whose ring
        # only shed older ones; a ring of the same size, `_n_lost`
        # written since clear()
        self._lost_ends = array("d", bytes(8 * n))
        self._n_lost = 0
        # events whose writer could not wait for _lock (the collector's
        # callback, on a thread that may hold it), as _write_locked's
        # arguments: appended without the lock, written by its next
        # holder
        self._late: list = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    def _write(self, name, ts, dur, id_, parent, cause, req, args) -> None:
        with self._lock:
            self._write_locked(threading.get_ident(), name, ts, dur, id_,
                               parent, cause, req, args)
            if self._late:
                self._take_late_locked()

    def _write_locked(self, tid, name, ts, dur, id_, parent, cause, req,
                      args) -> None:
        i = self._written % self._capacity
        if self._written >= self._capacity:     # slot i: the oldest
            self.dropped += 1
            self._lost_ends[self._n_lost % self._capacity] = \
                self._ts[i] + max(0.0, self._dur[i])
            self._n_lost += 1
            self._objs.pop(i, None)
            note_dropped()
        self._written += 1
        self._name[i] = _NAMES.get(name, name)
        self._ts[i], self._dur[i] = ts, dur
        k = _INTS * i
        ints = self._ints
        ints[k + _TID], ints[k + _ID] = tid, id_
        ints[k + _PARENT], ints[k + _CAUSE] = parent, cause or 0
        if _fits(req):
            ints[k + _REQ], req = req, None
        else:
            ints[k + _REQ] = _NO_REQ
        key = None
        if args and len(args) == 1:
            (key, value), = args.items()
            if _fits(value):
                ints[k + _ARG], args = value, None
            else:
                key = None
        self._argkey[i] = key
        if req is not None or args:
            self._objs[i] = (req, args)

    def _take_late_locked(self) -> None:
        while self._late:
            self._write_locked(*self._late.pop(0))

    def _slots_locked(self) -> range:
        """The live slots' positions, oldest first (slot = pos % n)."""
        return range(max(0, self._written - self._capacity), self._written)

    def _fields_locked(self, i: int):
        """(tid, id, parent, cause or None, req or None, args) of slot i."""
        tid, id_, parent, cause, req, value = \
            self._ints[_INTS * i:_INTS * (i + 1)]
        obj_req, args = self._objs.get(i, (None, None))
        if self._argkey[i] is not None:
            args = {self._argkey[i]: value}
        return (tid, id_, parent, cause or None,
                obj_req if req == _NO_REQ else req, args or {})

    # ------------------------------------------------------------ record

    def _ts_us(self, t_s: float) -> float:
        return (t_s - self._t0) * 1e6

    def instant(self, name: str, req=None, **args) -> None:
        """One point-in-time marker ("i" phase)."""
        if not _state.enabled:
            return
        self._write(name, self._ts_us(time.perf_counter()), -1.0, 0, 0,
                    None, req, args)

    def _record_span(self, name, start_s, end_s, id_, parent, cause, req,
                     args) -> None:
        self._write(name, self._ts_us(start_s),
                    max(0.0, (end_s - start_s) * 1e6), id_, parent, cause,
                    req, args)

    def complete(self, name: str, start_s: float, end_s: float,
                 req=None, cause=None, **args) -> None:
        """One complete ("X") event from perf_counter() start/end stamps
        — the shape callers use when the interval isn't a `with` block
        (consensus step intervals close when the NEXT step begins). It
        takes a span's fields: an id of its own, and as parent the
        innermost span open on the calling thread."""
        if not _state.enabled:
            return
        stack = _open_spans()
        self._record_span(name, start_s, end_s, next(_ids),
                          stack[-1].id if stack else 0, cause, req, args)

    def span(self, name: str, req=None, cause=None, **args):
        """Context manager timing a block as one complete event; `as`
        gives the span, whose `id` another thread can take as its
        `cause`."""
        if not _state.enabled:
            return _NULL_SPAN
        return _Span(self, name, req, cause, args)

    # ------------------------------------------------------------- dump

    def events(self) -> list:
        """The ring as Chrome-trace events, oldest first."""
        pid = os.getpid()
        out = []
        with self._lock:
            self._take_late_locked()
            for pos in self._slots_locked():
                i = pos % self._capacity
                tid, id_, parent, cause, req, args = self._fields_locked(i)
                ev = {"name": self._name[i]}
                if self._dur[i] < 0:
                    ev.update(ph="i", s="t", ts=self._ts[i], pid=pid, tid=tid)
                else:
                    ev.update(ph="X", ts=self._ts[i], dur=self._dur[i],
                              pid=pid, tid=tid, id=id_, parent=parent)
                    if cause is not None:
                        ev["cause"] = cause
                if req is not None:
                    ev["req"] = req
                if args:
                    ev["args"] = args
                out.append(ev)
        return out

    def clear(self) -> None:
        """Empty the ring. `dropped` counts over the tracer's life and
        stays; what `between` holds against a window goes."""
        with self._lock:
            self._written = self._n_lost = 0
            self._objs.clear()
            del self._late[:]

    def between(self, name: str, t0: float,
                t1: float) -> Tuple[List[dict], int]:
        """What a reader with a window on time.perf_counter needs:
        (the events called `name` that overlap [t0, t1], how many
        events the ring displaced that ended at or after t0).
        Each row has `start` and `end` (perf_counter seconds, not
        clipped; an instant has both equal), `tid`, `id`, `parent`,
        `cause`, `req` and `args`. A count above 0 says the rows may be
        incomplete."""
        lo, hi = self._ts_us(t0), self._ts_us(t1)
        rows = []
        with self._lock:
            self._take_late_locked()
            lost = self._lost_ends[:min(self._n_lost, self._capacity)]
            dropped = sum(1 for end in lost if end >= lo)
            for pos in self._slots_locked():
                i = pos % self._capacity
                if self._name[i] != name:
                    continue
                ts = self._ts[i]
                end = ts + max(0.0, self._dur[i])
                if end < lo or ts > hi:
                    continue
                tid, id_, parent, cause, req, args = self._fields_locked(i)
                rows.append({"start": self._t0 + ts / 1e6,
                             "end": self._t0 + end / 1e6, "tid": tid,
                             "id": id_, "parent": parent, "cause": cause,
                             "req": req, "args": args})
        return rows, dropped

    def chrome_trace(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON; returns the path. Loadable in
        chrome://tracing or https://ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# Process-wide tracer (the consensus timeline all nodes in-process share;
# events carry pid/tid so merged timelines stay distinguishable).
TRACER = Tracer()


def span(name: str, req=None, cause=None, **args):
    return TRACER.span(name, req, cause, **args)


def complete(name: str, start_s: float, end_s: float, req=None, cause=None,
             **args) -> None:
    TRACER.complete(name, start_s, end_s, req, cause, **args)


def instant(name: str, req=None, **args) -> None:
    TRACER.instant(name, req, **args)


def dump_trace(path: str) -> str:
    return TRACER.dump(path)


# ---------------------------------------------------- the cyclic collector

# A collection shorter than this moves the counter and gets no event: a
# node under load runs hundreds of generation-0 collections a second,
# and the ring is for the steps.
GC_EVENT_MIN_S = 1e-3

_m_gc_pause = _REGISTRY.counter(
    "gc_pause_seconds_total",
    "Seconds the cyclic collector held the interpreter, by the "
    "generation collected", ("gen",))
# the family's three children, made here and not by labels() in the
# callback: that may take the family's lock, which the thread a
# collection stopped may hold (_Family.children allocates under it)
with _m_gc_pause._lock:
    _gc_pause_of_gen = tuple(
        _m_gc_pause._children.setdefault((str(gen),),
                                         _m_gc_pause._new_child())
        for gen in range(3))


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks entry. The event lies on the thread the collection
    stopped, inside whatever span was open there."""
    if phase == "start":
        _local.gc_started = time.perf_counter()
        return
    t1 = time.perf_counter()
    t0 = _local.__dict__.pop("gc_started", None)
    if t0 is None:      # switched on while this collection ran
        return
    _gc_pause_of_gen[info["generation"]].inc(t1 - t0)
    if t1 - t0 < GC_EVENT_MIN_S:
        return
    stack = _open_spans()
    event = (threading.get_ident(), "gc.collect", TRACER._ts_us(t0),
             (t1 - t0) * 1e6, next(_ids), stack[-1].id if stack else 0,
             None, stack[-1].req if stack else None,
             {"gen": info["generation"], "collected": info["collected"]})
    # never wait for the ring in here: the thread that holds its lock
    # may be the one this collection stopped. Its next holder writes
    # what is left for it
    if not TRACER._lock.acquire(False):
        TRACER._late.append(event)
        return
    try:
        TRACER._write_locked(*event)
    finally:
        TRACER._lock.release()


def _watch_gc(on: bool) -> None:
    """The callback is in gc.callbacks while telemetry is on, and only
    then: switched off, the interpreter calls nothing of ours."""
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


_on_enabled.append(_watch_gc)
_watch_gc(_state.enabled)
