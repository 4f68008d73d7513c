"""FuzzedLink — chaos wrapper for connection links (p2p/fuzz.go).

Wraps any link (write/read/close) and randomly drops writes, delays
reads/writes, or kills the connection — the reference's FuzzedConnection
with mode=drop (p=0.2 default) / mode=delay (:10-47). Used by tests to
assert reactors survive a lossy transport.

Two extensions over the reference:

- Vectored passthrough (ISSUE 4 satellite): burst-mode links
  (SecretConnection/PlainFramedConn `write_many`/`read_burst`) are
  fuzzed PER FRAME, so a connection that upgraded to the burst frame
  plane (PR 3) cannot silently bypass fault injection. When the inner
  link lacks the vectored API the wrapper degrades to per-frame calls,
  so FuzzedLink always presents the full link surface.

- Deterministic decider: a `decider(op)` callable replaces the
  probability draws with externally scheduled decisions — the chaos
  plane's FaultSchedule drives drop/delay deterministically from one
  seed. Return None/"pass" to deliver, "drop" to drop, ("delay", s) to
  sleep s seconds first. `on_fault(kind)` observes every injected
  fault (telemetry counting lives in tendermint_tpu.chaos, not here).

- A set delay (serving/topology.py's delay by region, through
  config.p2p.region_delay_ms): mode "delay" with `delay_s` above 0
  holds every frame written for `delay_s` plus a draw of up to
  `jitter_s` from the link's seeded generator, and never lets a frame
  overtake one written before it (a TCP link keeps its order). On the
  thread plane the writer sleeps, as the reference's does. On the loop
  plane a sleep would stop every connection of the node, so the bytes
  are sealed at once (the nonce order is the wire order) and handed to
  the connection when they are due (`attach_loop`). Frames sealed
  together leave together, when the last of them is due. Each frame's
  hold is observed in `tm_p2p_link_delay_seconds`.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from dataclasses import dataclass

from tendermint_tpu import telemetry

_m_link_delay = telemetry.histogram(
    "p2p_link_delay_seconds",
    "Seconds a delaying link (config.p2p.region_delay_ms) held a frame "
    "before it went to the socket",
    buckets=(.005, .01, .02, .03, .04, .05, .06, .08, .1, .12, .15, .2,
             .3, .5, 1.0))


@dataclass
class FuzzConfig:
    """p2p/fuzz.go FuzzConnConfig defaults (:39-47)."""
    mode: str = "drop"              # "drop" | "delay"
    max_delay_s: float = 0.3
    prob_drop_rw: float = 0.2
    prob_drop_conn: float = 0.0
    prob_sleep: float = 0.0
    seed: int | None = None
    delay_s: float = 0.0            # mode "delay": hold every write
    jitter_s: float = 0.0           # ... plus up to this, drawn per frame


class FuzzedLink:
    def __init__(self, link, config: FuzzConfig | None = None,
                 decider=None, on_fault=None):
        self.link = link
        self.config = config or FuzzConfig()
        self.decider = decider
        self.on_fault = on_fault
        self._rng = random.Random(self.config.seed)
        self._lock = threading.Lock()
        self._dead = False
        # a set delay: the loop and the sink that takes held bytes
        # (attach_loop), and when the last held frame is due
        self._loop = None
        self._sink = None
        self._last_due = 0.0
        self._held = deque()    # sealed bytes not yet due, oldest first

    def _note(self, kind: str) -> None:
        if self.on_fault is not None:
            self.on_fault(kind)

    def _fuzz(self, op: str = "rw") -> bool:
        """True = drop this operation (fuzz.go:132)."""
        if self.decider is not None:
            with self._lock:
                if self._dead:
                    raise ConnectionError("fuzzed connection killed")
                action = self.decider(op)
            if action in (None, "pass"):
                return False
            if action == "drop":
                self._note("drop")
                return True
            if isinstance(action, tuple) and action[0] == "delay":
                self._note("delay")
                time.sleep(action[1])
                return False
            raise ValueError(f"unknown fuzz action {action!r}")
        cfg = self.config
        with self._lock:
            if self._dead:
                raise ConnectionError("fuzzed connection killed")
            if cfg.mode == "drop":
                if cfg.prob_drop_conn > 0 and \
                        self._rng.random() < cfg.prob_drop_conn:
                    self._dead = True
                    self._note("kill")
                    raise ConnectionError("fuzzed connection killed")
                if self._rng.random() < cfg.prob_drop_rw:
                    self._note("drop")
                    return True
            elif cfg.mode == "delay":
                if cfg.prob_sleep > 0 and self._rng.random() < cfg.prob_sleep:
                    self._note("delay")
                    time.sleep(self._rng.random() * cfg.max_delay_s)
        return False

    def _hold_s(self, n_frames: int) -> float:
        """Seconds from now until `n_frames` written together may go
        out: 0.0 unless the mode is "delay" with a delay set."""
        cfg = self.config
        if cfg.mode != "delay" or cfg.delay_s <= 0 or n_frames <= 0:
            return 0.0
        now = time.monotonic()
        with self._lock:
            jitter = max(self._rng.random() for _ in range(n_frames)) \
                * cfg.jitter_s
            due = max(now + cfg.delay_s + jitter, self._last_due + 1e-6)
            self._last_due = due
        if telemetry.enabled():
            for _ in range(n_frames):
                _m_link_delay.observe(due - now)
        return due - now

    def attach_loop(self, loop, sink) -> None:
        """The loop plane's connection (LoopMConnection) takes what a
        set delay holds back: `sink(wire)` runs on `loop`'s thread when
        the bytes are due."""
        self._loop, self._sink = loop, sink

    def write(self, data: bytes) -> int:
        if self._fuzz("write"):
            return len(data)  # silently dropped
        hold = self._hold_s(1)
        if hold > 0:
            time.sleep(hold)
        return self.link.write(data)

    def write_many(self, chunks) -> int:
        """Per-frame fuzz over a burst: survivors still go out as ONE
        vectored write when the substrate supports it (the wire stays
        burst-framed); callers observe full acceptance, dropped frames
        just never reach the wire — same contract as write()."""
        chunks = list(chunks)
        kept = [c for c in chunks if not self._fuzz("write")]
        if kept:
            hold = self._hold_s(len(kept))
            if hold > 0:
                time.sleep(hold)
            inner = getattr(self.link, "write_many", None)
            if inner is not None:
                inner(kept)
            else:
                for c in kept:
                    self.link.write(c)
        return sum(len(c) for c in chunks)

    def read(self) -> bytes:
        while True:
            frame = self.link.read()
            if frame == b"":
                return b""
            if self._fuzz("read"):
                continue  # drop received frame
            return frame

    def read_burst(self):
        """Per-frame fuzz over a received burst; loops until at least
        one frame survives ([] only on clean EOF, matching the burst
        link contract)."""
        inner = getattr(self.link, "read_burst", None)
        while True:
            if inner is not None:
                frames = inner()
            else:
                f = self.link.read()
                frames = [f] if f != b"" else []
            if not frames:
                return []
            kept = [f for f in frames if not self._fuzz("read")]
            if kept:
                return kept

    def seal_frames(self, chunks) -> bytes:
        """Loop-reactor codec surface: per-frame fuzz applied BEFORE the
        inner seal, so a loop-mode connection cannot bypass fault
        injection; survivors seal in one inner burst (wire stays
        burst-framed). Dropped frames simply never reach the wire."""
        kept = [c for c in chunks if not self._fuzz("write")]
        if not kept:
            return b""
        wire = self.link.seal_frames(kept)
        hold = self._hold_s(len(kept)) if self._sink is not None else 0.0
        if hold <= 0:
            return wire
        # one timer a burst, and each timer releases the OLDEST burst:
        # two timers a microsecond apart may fire in either order, the
        # bytes may not
        self._held.append(wire)
        self._loop.call_later(hold, self._release, owner="p2p")
        return b""

    def _release(self) -> None:
        if self._held:
            self._sink(self._held.popleft())

    def feed_wire(self, data: bytes):
        """Loop-reactor codec surface: inner decode, then per-frame
        read fuzz over the decoded burst. [] just means nothing
        survived this readiness event (the loop, unlike read_burst's
        blocking contract, never interprets [] as EOF)."""
        frames = self.link.feed_wire(data)
        return [f for f in frames if not self._fuzz("read")]

    def close(self) -> None:
        self.link.close()
