"""The program's own spans, for the `ycsb_` readers (the recorder of
tendermint_tpu/telemetry/trace.py, through benchmark/program_spans.py).

In this cell four validators commit a block every 140 ms and answer
eighty reads a second, and together they write the ring (65,536 events)
full in under a minute: a reader that came after the drain and the
checks found the window's events displaced. So the driver takes the
rows it needs out of the ring every few seconds of the window
(`Harvest`), and the readers read what it took. Every function returns
None where the program has no such span (a parent commit) or the ring
lost events of the window all the same."""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional

from benchmark import program_spans
from benchmark.stats import percentile

EVERY_S = 4.0       # between two takes: some 5,000 events of the ring


class Harvest:
    """Rows of the spans called `names`, by name, as they are taken."""

    def __init__(self, names: Iterable[str]):
        trace = program_spans.tracer()
        self._tracer = None if trace is None else trace.TRACER
        self._rows: Dict[str, Optional[dict]] = {
            name: {} if trace is not None and name in trace.SPANS else None
            for name in names}
        self._since: Optional[float] = None

    def take(self, now: float) -> None:
        """The events that overlap the time since the last take (the
        first take only sets the mark). A span open across a take is
        seen by the next one, and by its id only once."""
        since, self._since = self._since, now
        if since is None or self._tracer is None:
            return
        for name, kept in self._rows.items():
            if kept is None:
                continue
            rows, dropped = self._tracer.between(name, since, now)
            if dropped:
                self._rows[name] = None     # the ring outran the takes
                continue
            for row in rows:
                kept[row["id"]] = {"start": row["start"], "end": row["end"],
                                   "args": row["args"]}

    def rows(self) -> Dict[str, Optional[List[dict]]]:
        return {name: None if kept is None else list(kept.values())
                for name, kept in self._rows.items()}


def began_inside(r, name: str,
                 keep: Optional[Callable[[dict], object]] = None
                 ) -> Optional[List[dict]]:
    """The harvested events called `name` that began inside the
    window, those whose args `keep` accepts."""
    rows = (r.client.get("span_rows") or {}).get(name)
    if rows is None:
        return None
    t0, t1 = r.window
    return [row for row in rows if t0 <= row["start"] <= t1 and
            (keep is None or keep(row["args"]))]


def median_ms(r, name: str, keep=None) -> Optional[float]:
    rows = began_inside(r, name, keep)
    return None if rows is None else percentile(
        [1000.0 * (row["end"] - row["start"]) for row in rows], 0.5)


def median_arg(r, name: str, arg: str) -> Optional[float]:
    rows = began_inside(r, name)
    return None if rows is None else percentile(
        [row["args"][arg] for row in rows if arg in row["args"]], 0.5)
