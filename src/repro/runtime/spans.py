"""Named spans and counters of the program's own work.

``Spans.span(name, **attrs)`` times a block of host code.  It opens a
``jax.profiler.TraceAnnotation`` (so a profiler trace shows the block on
the same clock as the device's operations, with ``attrs`` as the event's
stats) and, when the block ends, adds its seconds to a per-name total and
count, and its self time (its seconds less those of the spans opened
inside it) to a per-name self total.  ``count(name, n)`` adds to a plain
counter.  ``snapshot()`` returns both tables; :func:`delta` of two
snapshots is what happened between them.

It is always on.  With the profiler off a span costs two clock reads, a
few dict updates and an inactive TraceMe.  One ``Spans`` belongs to one
owner (an engine, a trainer) driven by one thread at a time.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict, List

import jax


class Span:
    """One open span.  ``name`` may change before the block ends: the
    totals take the name the span ends with, the profiler keeps the one it
    opened with.  ``seconds`` holds the duration once it has ended."""

    __slots__ = ("name", "seconds", "_owner", "_trace", "_t0", "_children")

    def __init__(self, owner: "Spans", name: str, attrs):
        self.name = name
        self.seconds = 0.0
        self._owner = owner
        self._trace = jax.profiler.TraceAnnotation(name, **attrs)
        self._children = 0.0

    def __enter__(self) -> "Span":
        self._trace.__enter__()
        self._owner._open.append(self)
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = perf_counter() - self._t0
        self._trace.__exit__(*exc)
        owner = self._owner
        owner._open.pop()
        if owner._open:
            owner._open[-1]._children += dt
        self.seconds = dt
        row = owner._rows.get(self.name)
        if row is None:
            row = owner._rows[self.name] = [0, 0.0, 0.0]
        row[0] += 1
        row[1] += dt
        row[2] += dt - self._children


class Spans:
    def __init__(self):
        self._rows: Dict[str, List] = {}      # name -> [count, total s, self s]
        self._counts: Dict[str, int] = {}
        self._open: List[Span] = []

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def count(self, name: str, n: int = 1) -> None:
        self._counts[name] = self._counts.get(name, 0) + n

    def total(self, name: str) -> float:
        """Seconds of every ended span of this name."""
        row = self._rows.get(name)
        return row[1] if row is not None else 0.0

    def snapshot(self) -> Dict[str, Dict]:
        return {
            "spans": {k: {"count": n, "total_s": t, "self_s": s}
                      for k, (n, t, s) in self._rows.items()},
            "counts": dict(self._counts),
        }


def delta(before: Dict[str, Dict], after: Dict[str, Dict]) -> Dict[str, Dict]:
    """What two snapshots of one ``Spans`` differ by (names that did not
    move are left out)."""
    spans = {}
    for name, row in after["spans"].items():
        old = before["spans"].get(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        if row["count"] != old["count"]:
            spans[name] = {k: row[k] - old[k] for k in row}
    counts = {k: n - before["counts"].get(k, 0) for k, n in after["counts"].items()
              if n != before["counts"].get(k, 0)}
    return {"spans": spans, "counts": counts}
