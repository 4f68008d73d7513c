"""Whole passes of fixed work.

A chain cell's window is filled with passes over the same work. At
least `min_passes` run, and another starts only while the time left is
at least the longest cycle (pass plus what is done between passes) so
far, so no pass is cut and the window is overrun by little. The rate
is the work of the whole passes over the sum of THEIR times: nothing is
counted at a cut-off, and the time between passes is not in it."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional


@dataclass
class Pass:
    start: float        # perf_counter at the start of the timed part
    seconds: float      # the timed part alone
    work: int           # commits offered in the pass
    failed: int = 0     # genuine commits the pass rejected
    extra: dict = field(default_factory=dict)


def run_passes(one_pass: Callable[[object], Pass], seconds: float,
               between: Optional[Callable[[], object]] = None,
               min_passes: int = 2,
               clock: Callable[[], float] = time.perf_counter) -> List[Pass]:
    """`between()` (untimed: decode, drop the last pass's objects,
    collect) returns what `one_pass` takes; `one_pass` times itself and
    returns its Pass."""
    passes: List[Pass] = []
    opened = clock()
    longest_cycle = 0.0
    while True:
        t_cycle = clock()
        prepared = between() if between is not None else None
        passes.append(one_pass(prepared))
        longest_cycle = max(longest_cycle, clock() - t_cycle)
        left = seconds - (clock() - opened)
        if len(passes) >= min_passes and left < longest_cycle:
            return passes


def rate(passes: List[Pass]) -> Optional[float]:
    """Work of the whole passes over the sum of their own times."""
    t = sum(p.seconds for p in passes)
    return sum(p.work for p in passes) / t if passes and t > 0 else None
