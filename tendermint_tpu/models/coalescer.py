"""Dispatch coalescer — cross-call dynamic micro-batching for the
BatchVerifier.

The paper's headline win comes from batching at the VoteSet.AddVote /
VerifyCommit boundary, but in live consensus votes arrive ONE AT A TIME
from many concurrent peer/reactor threads: every call lands in
`BatchVerifier.verify_async` as a batch of 1 and takes the scalar host
path, so the device never sees the aggregate arrival rate. This module
is the standard inference-serving answer (continuous/dynamic batching):
sub-threshold calls enqueue their items into a shared queue and get
back a future-style resolver; a dispatcher thread drains the queue,
forms ONE merged batch per window, hands it to the verifier's direct
dispatch path (which applies the normal routing — scalar below the
auto threshold, device above, secp256k1 split to host), and demuxes
the verdicts back to each caller in submission order.

Batching policy (the knobs are TM_TPU_COALESCE / TM_TPU_COALESCE_WAIT_MS
/ TM_TPU_COALESCE_MAX_BATCH and config.base.verifier_coalesce_*):

  - Only backend `jax` merges. The one thing a merge can change is
    whether a batch crosses the verifier's auto_threshold and runs on
    the device: under it the merged batch is verified on the host
    signature by signature, as its calls would have been alone, and
    live votes never come near it (a handful a burst, some 150 calls a
    second in a four-validator process). So the verifier `submit`s a
    sub-threshold call only where every call is the device's and fewer
    dispatches are always worth a hand-over; for `auto` and `python` it
    asks for an `inline` resolver, which runs the direct path on the
    thread that resolves it (`verify` is `verify_async(...)()`: the
    caller's own, at once). Nothing is queued, the dispatcher thread
    is not started, and an exception is the caller's own.
  - The dispatcher wakes on the first queued arrival and then LINGERS
    only while traffic is dense: it keeps collecting until no new call
    has arrived for ~4x the EWMA inter-arrival gap, capped at max_wait
    (default 2ms) from the first drain, or until max_batch items
    (default BATCH_CHUNK) are queued. A solo sequential caller —
    whose inter-arrival gap is its own verify latency, necessarily
    above the cap — therefore dispatches immediately and pays only a
    thread handoff, while a burst of reactor threads merges into one
    batch per wave. This is the "adaptive max-wait tuned by arrival
    rate" split: latency for sparse traffic, throughput for dense.

Per-call error semantics are preserved by ISOLATION FALLBACK: if the
merged dispatch (or its resolution) raises, every call is re-dispatched
individually so one caller's malformed items surface as that caller's
exception while everyone else still gets verdicts.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Sequence

import numpy as np

from tendermint_tpu import telemetry
from tendermint_tpu.telemetry import queues as queue_obs

# Catalog in docs/observability.md. The coalesce FACTOR — the number the
# tentpole is judged on — is coalesce_calls_total / dispatches_total,
# or the mean of the batch_calls histogram over a scrape window.
_m_calls = telemetry.counter(
    "verifier_coalesce_calls_total",
    "verify calls routed through the dispatch coalescer")
_m_inline = telemetry.counter(
    "verifier_coalesce_inline_total",
    "verify calls left to their caller's thread: nothing a merge could "
    "lift onto the device")
_m_dispatches = telemetry.counter(
    "verifier_coalesce_dispatches_total",
    "Merged dispatches formed by the coalescer")
_m_factor = telemetry.histogram(
    "verifier_coalesce_batch_calls",
    "verify() calls merged into one coalesced dispatch",
    buckets=telemetry.POW2_BUCKETS)
_m_queue = telemetry.histogram(
    "verifier_coalesce_queue_depth",
    "Calls pending in the coalescer queue at first drain",
    buckets=telemetry.POW2_BUCKETS)
_m_wait = telemetry.histogram(
    "verifier_coalesce_wait_seconds",
    "Per-call wait from submit to merged dispatch",
    buckets=(.0002, .0005, .001, .002, .004, .008, .016, .05, .1, .5))
_m_fallback = telemetry.counter(
    "verifier_coalesce_fallback_total",
    "Merged dispatches re-run per-call for error isolation")


class _Merged:
    """Shared result of one merged dispatch. The dispatcher never blocks
    on device results — the FIRST caller to resolve materializes the
    merged verdict array (under a once-lock), every other caller slices
    it. Failures demote the whole merged batch to per-call dispatches so
    exceptions stay with the call that caused them."""

    __slots__ = ("_dispatch", "calls", "_resolver", "_per", "_value",
                 "_done", "_lock")

    def __init__(self, dispatch: Callable, calls: list):
        self._dispatch = dispatch
        self.calls = calls
        self._resolver = None
        self._per = None      # per-call (kind, payload) after fallback
        self._value = None
        self._done = False
        self._lock = threading.Lock()

    def dispatch(self, items: list) -> None:
        """Run on the dispatcher thread: enqueue the merged batch."""
        try:
            self._resolver = self._dispatch(items)
        except Exception:
            self._isolate()

    def _isolate(self) -> None:
        """Per-call fallback: each caller gets its own dispatch outcome
        (resolver or exception) instead of sharing the batch's."""
        _m_fallback.inc()
        per = []
        for c in self.calls:
            try:
                per.append(("r", self._dispatch(c.items)))
            except Exception as e:  # this caller's own failure
                per.append(("e", e))
        self._per = per

    def result_for(self, call: "_Call") -> np.ndarray:
        with self._lock:
            if not self._done:
                if self._per is None:
                    try:
                        self._value = np.asarray(self._resolver())
                    except Exception:
                        self._isolate()
                self._done = True
        if self._per is None:
            return self._value[call.lo:call.lo + call.n]
        kind, payload = self._per[call.idx]
        if kind == "e":
            raise payload
        return np.asarray(payload())


class _Call:
    __slots__ = ("items", "n", "t_submit", "event", "merged", "lo", "idx")

    def __init__(self, items: list, t_submit: float):
        self.items = items
        self.n = len(items)
        self.t_submit = t_submit
        self.event = threading.Event()
        self.merged = None
        self.lo = 0
        self.idx = 0

    def resolve(self) -> np.ndarray:
        self.event.wait()
        return self.merged.result_for(self)


class _Inline:
    """A call that no merge could lift onto the device: dispatched and
    resolved by the thread that asks for its verdicts, and kept for one
    that asks again. Two threads that ask at once both verify, to the
    same verdicts."""

    __slots__ = ("_dispatch", "_items", "_value")

    def __init__(self, dispatch: Callable, items: list):
        self._dispatch = dispatch
        self._items = items
        self._value = None

    def resolve(self) -> np.ndarray:
        if self._value is None:
            self._value = np.asarray(self._dispatch(self._items)())
        return self._value


class DispatchCoalescer:
    """Merge concurrent verify calls into batched dispatches.

    dispatch: callable(items) -> zero-arg resolver — the verifier's
    DIRECT (non-coalescing) async path; it must never re-enter the
    coalescer or the dispatcher deadlocks on itself.
    """

    def __init__(self, dispatch: Callable, max_batch: int = 8192,
                 max_wait_s: float = 0.002):
        if max_batch < 1:
            raise ValueError(f"coalesce max_batch must be >= 1, "
                             f"got {max_batch}")
        if max_wait_s < 0:
            raise ValueError(f"coalesce max_wait must be >= 0, "
                             f"got {max_wait_s}")
        self._dispatch = dispatch
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self._cond = threading.Condition()
        self._queue: list[_Call] = []      #: guarded_by _cond
        self._closed = False               #: guarded_by _cond
        # EWMA inter-arrival gap, seeded sparse (= no lingering) so the
        # first calls after startup never pay the window
        self._ewma_gap = max(max_wait_s, 1e-4)  #: guarded_by _cond
        self._last_arrival = 0.0           #: guarded_by _cond
        # the dispatcher thread is LAZY and self-reaping: spawned on the
        # first submit, exits after idle_timeout_s without traffic (and
        # respawns on the next submit) — so short-lived verifiers don't
        # accumulate parked threads for the process lifetime
        self.idle_timeout_s = 30.0
        self._running = False              #: guarded_by _cond
        self._thread = None                #: guarded_by _cond
        # queue observatory: items waiting for a merged dispatch vs the
        # early-out bound (an unlocked sum over a short list — a torn
        # read costs one slightly-stale gauge sample)
        self._queue_probe = queue_obs.register(
            "verifier.coalesce", self,
            depth=lambda c: sum(call.n for call in c._queue),
            capacity=max_batch)

    # ------------------------------------------------------------ callers

    def submit(self, items: Sequence) -> Callable[[], np.ndarray]:
        """Enqueue one call's items; returns a zero-arg resolver yielding
        this call's own bool[N] verdicts (or raising this call's own
        dispatch failure). Blocks only inside the resolver."""
        now = time.perf_counter()
        call = _Call(list(items), now)
        with self._cond:
            if self._closed:
                raise RuntimeError("coalescer is closed")
            if self._last_arrival:
                gap = now - self._last_arrival
                self._ewma_gap += 0.25 * (gap - self._ewma_gap)
            self._last_arrival = now
            self._queue.append(call)
            if not self._running:
                self._running = True
                self._thread = threading.Thread(
                    target=self._run, name="tm-verify-coalesce",
                    daemon=True)
                self._thread.start()
            self._cond.notify()
        _m_calls.inc()
        return call.resolve

    def inline(self, items: Sequence) -> Callable[[], np.ndarray]:
        """The resolver of a call that is not worth a hand-over: its
        dispatch runs on the thread that resolves it, its exception is
        that thread's own, and the dispatcher never hears of it.
        Counted among the calls, and as inline."""
        _m_calls.inc()
        _m_inline.inc()
        return _Inline(self._dispatch, list(items)).resolve

    def close(self, timeout: float = 5.0) -> None:
        """Stop the dispatcher; queued calls are still dispatched."""
        self._queue_probe.close()
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout)

    # --------------------------------------------------------- dispatcher

    def _window_s_locked(self) -> float:
        """Linger budget for the current drain: ~4 inter-arrival gaps
        when traffic is dense enough that more arrivals are imminent,
        zero when the EWMA gap says waiting can't coalesce anything."""
        gap = self._ewma_gap
        if gap >= self.max_wait_s:
            return 0.0
        return min(self.max_wait_s, 4.0 * gap)

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    if not self._cond.wait(self.idle_timeout_s):
                        if not self._queue and not self._closed:
                            # idle: reap this thread; the next submit
                            # respawns one (the re-check is atomic with
                            # the flag — wait() reacquired the lock)
                            self._running = False
                            return
                if not self._queue and self._closed:
                    self._running = False
                    return
                t0 = time.perf_counter()
                calls = self._queue
                self._queue = []
                n = sum(c.n for c in calls)
                if telemetry.enabled():
                    _m_queue.observe(len(calls))
                # linger for the rest of the burst: quiesce after ~4
                # gaps without a new arrival, hard cap max_wait from
                # the first drain, early out at max_batch
                hard = t0 + self.max_wait_s
                deadline = t0 + self._window_s_locked()
                while not self._closed and n < self.max_batch:
                    now = time.perf_counter()
                    if now >= deadline:
                        break
                    self._cond.wait(deadline - now)
                    if self._queue:
                        calls += self._queue
                        self._queue = []
                        n = sum(c.n for c in calls)
                        deadline = min(
                            hard, time.perf_counter() + self._window_s_locked())
            self._dispatch_merged(calls)

    def _dispatch_merged(self, calls: list) -> None:
        items = []
        for idx, c in enumerate(calls):
            c.idx = idx
            c.lo = len(items)
            items.extend(c.items)
        merged = _Merged(self._dispatch, calls)
        merged.dispatch(items)
        if telemetry.enabled():
            now = time.perf_counter()
            _m_dispatches.inc()
            _m_factor.observe(len(calls))
            for c in calls:
                _m_wait.observe(now - c.t_submit)
        for c in calls:
            c.merged = merged
            c.event.set()
