"""One run of one cell: set-up, the measured window, the check of what
the window produced, and the result line. Drivers (drivers/<name>.py)
do the cell's work through this object; metric readers
(metrics/<name>.py) read the Reading it leaves."""

from __future__ import annotations

import gc
import json
import os
import shutil
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchmark import trace_reduce
from benchmark.manifest import Manifest
from benchmark.passes import Pass, run_passes
from benchmark.stats import quartile_spread
from benchmark.spans import SpanLog

TRACE_DIR = ".bench_trace"      # inside the checkout, in .gitignore


class WindowCompiled(RuntimeError):
    """Something compiled inside the measured window."""


@dataclass
class Outcome:
    """What a driver hands back."""
    attempted: int
    failed: int
    passes: List[Pass] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    client: Dict[str, object] = field(default_factory=dict)


@dataclass
class Reading:
    """What a metric reader sees. A reader that finds nothing to read
    returns None and its metric is left out of the line."""
    setup_s: float
    window: tuple                   # (t0, t1) on time.perf_counter
    spans: SpanLog
    passes: List[Pass]
    counters: Dict[str, float]
    client: Dict[str, object]
    trace: Optional[dict]           # trace_reduce's result, traced runs
    compiles_in_window: int
    setup_compile_s: float
    memory_peak_bytes: Optional[int]


class Harness:
    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, rehearsal: bool = False,
                 overrides: Optional[dict] = None,
                 t_start: Optional[float] = None):
        self.root = root
        self.manifest = Manifest(root)
        self.cell = self.manifest.cell(workload)
        self.config = self.manifest.config(self.cell["config"])
        self.traffic = self.manifest.traffic(self.cell)
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.rehearsal = rehearsal
        self.params = {k: v for k, v in self.config.items()
                       if k != "rehearsal"}
        self.params.update(self.traffic.get("params", {}))
        if rehearsal:
            self.params.update(self.config.get("rehearsal", {}))
            self.params.update(self.traffic.get("rehearsal", {}))
        self.params.update(overrides or {})
        self.t_start = time.time() if t_start is None else t_start
        self.spans = SpanLog(annotate=trace and not rehearsal)
        self.checks: List[dict] = []
        self.setup_s: Optional[float] = None
        self.window = (None, None)
        self._traces: List[dict] = []
        self._n_profiles = 0
        from benchmark.compiles import CompileWatch
        self.compiles = CompileWatch()

    # ------------------------------------------------------- what drivers use

    def note(self, kind: str, **fields) -> None:
        print(json.dumps({"bench": kind, **fields}, sort_keys=True,
                         default=str), flush=True)

    def check(self, name: str, value, limit) -> bool:
        """One number compared beside its limit (value <= limit is
        sound); printed in every run, and part of `correct`."""
        ok = value is not None and value <= limit
        self.checks.append({"check": name, "value": value, "limit": limit,
                            "ok": ok})
        self.note("check", check=name, value=value, limit=limit, ok=ok)
        return ok

    def settle(self) -> None:
        """After set-up: nothing the harness built is walked by the
        collector again. The collector stays on."""
        gc.collect()
        gc.freeze()

    def open_window(self) -> float:
        self.setup_s = time.time() - self.t_start
        t0 = time.perf_counter()
        self.window = (t0, None)
        return t0

    def close_window(self) -> float:
        t1 = time.perf_counter()
        self.window = (self.window[0], t1)
        return t1

    def timed_passes(self, timed, between, verifier):
        """The window of a chain cell: whole passes of `timed(prepared)`
        with `between()` before each, under `verifier`'s counters. In a
        traced run the passes after the first, `profile_passes` of them,
        run under the profiler. Returns (passes, counter deltas)."""
        from benchmark import probe
        n_profile = int(self.params.get("profile_passes", 1)) \
            if self.trace else 0
        done = {"passes": 0, "sigs": 0.0}

        def one_pass(prepared) -> Pass:
            done["passes"] += 1
            if not 2 <= done["passes"] <= 1 + n_profile:
                return timed(prepared)
            before = verifier.stats["jax_sigs"]
            with self.profile():
                out = timed(prepared)
            done["sigs"] += verifier.stats["jax_sigs"] - before
            return out

        c0 = probe.counters(verifier)
        self.open_window()
        passes = run_passes(one_pass, self.seconds, between=between,
                            min_passes=int(self.params.get("min_passes", 2)))
        self.close_window()
        counters = probe.delta(probe.counters(verifier), c0)
        counters["profiled_jax_sigs"] = done["sigs"]
        secs = [q.seconds for q in passes]
        self.note("passes", seconds=secs, spread=quartile_spread(secs))
        return passes, counters

    def check_signatures(self, counters: dict, want: int) -> None:
        """Every signature the passes offered was verified, and (on the
        chip) verified on the device."""
        self.check("signatures_not_verified",
                   abs(want - counters["verifier.sigs"]), 0)
        if not self.rehearsal:
            self.check("signatures_off_device", counters["verifier.sigs"] -
                       counters["verifier.jax_sigs"], 0)

    @contextmanager
    def profile(self):
        """The profiler on for what is inside, in a traced run on the
        chip; a plain span otherwise."""
        if not self.trace or self.rehearsal:
            with self.spans.span(trace_reduce.WINDOW_SPAN):
                yield
            return
        import jax
        self._n_profiles += 1
        out = os.path.join(self.root, TRACE_DIR,
                           f"{self.cell['name']}-{self.seed}-"
                           f"{self._n_profiles}")
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            with self.spans.span(trace_reduce.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        for path in trace_reduce.find_xplanes(out):
            events = trace_reduce.read_xplane(path)
            self.note("trace", file=os.path.basename(path), planes={
                p: {ln: len(evs) for ln, evs in lines.items()}
                for p, lines in events["devices"].items()},
                host_spans=len(events["host_spans"]))
            self._traces.append(trace_reduce.reduce_events(events))
        shutil.rmtree(out, ignore_errors=True)

    # ---------------------------------------------------------------- result

    def result(self, outcome: Outcome, device: dict) -> dict:
        t0, t1 = self.window
        compiled = self.compiles.inside(t0, t1)
        if compiled:
            raise WindowCompiled(
                f"{len(compiled)} compile(s) inside the measured window: "
                f"{sorted(set(compiled))}")
        from benchmark import device as device_mod
        mem = None if self.rehearsal else device_mod.memory_peak_bytes()
        reading = Reading(
            setup_s=self.setup_s, window=self.window, spans=self.spans,
            passes=outcome.passes, counters=outcome.counters,
            client=outcome.client,
            trace=trace_reduce.merge(self._traces) if self.trace else None,
            compiles_in_window=len(compiled),
            setup_compile_s=self.compiles.seconds_before(t0),
            memory_peak_bytes=mem)
        kind = "per_layer" if self.trace else "end_to_end"
        metrics = {}
        for m in self.manifest.metrics(self.cell["name"], kind):
            value = self.manifest.reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev = dict(device, memory_peak_bytes=mem)
        line = {"correct": bool(self.checks) and
                all(c["ok"] for c in self.checks),
                "attempted": outcome.attempted, "failed": outcome.failed,
                "metrics": metrics, "device": dev}
        if self.trace and reading.trace:
            dev["busy_s"] = reading.trace["busy_s"]
            dev["window_s"] = reading.trace["window_s"]
            line["breakdown"] = {
                "device_ops": reading.trace["device_ops"],
                "idle_gaps": reading.trace["idle_gaps"]}
        return line


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, rehearsal: bool = False,
             overrides: Optional[dict] = None,
             t_start: Optional[float] = None) -> dict:
    """The whole run; returns the result line as a dict. `rehearsal`
    is for the test suite alone: toy sizes from the files' `rehearsal`
    groups, no look for a chip, and a device that says so. The command
    never sets it."""
    h = Harness(root, workload, seed, seconds, trace, rehearsal, overrides,
                t_start)
    from benchmark import device as device_mod
    if rehearsal:
        dev = dict(device_mod.describe(), rehearsal=True)
    else:
        dev = device_mod.require_tpu(int(h.cell["chips"]))
    h.note("start", workload=workload, seed=seed, seconds=seconds,
           trace=trace, device=dev, params={
               k: v for k, v in h.params.items()
               if isinstance(v, (int, float, bool)) or
               (isinstance(v, str) and len(v) < 40)},
           hashseed=os.environ.get("PYTHONHASHSEED"))
    driver = h.manifest.driver(h.traffic["driver"])
    outcome = driver.run(h)
    return h.result(outcome, dev)
