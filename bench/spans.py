"""The program's own spans in a run's profiler trace, clipped to the window.

``repro.observability.span`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>.<phase>`` around each phase of the serving engine's round
(``repro.engine.pump`` ... ``repro.engine.park``) and around the batcher's
``repro.batcher.launch`` and ``repro.batcher.retire``.  They land in the
profiler's trace on the clock its device operations use.

``bench.trace.reduce`` keeps only the benchmark's ``bench.*`` annotations, so
the readers of the engine's phases load the run's trace file again and take
the ``repro.*`` events from it.  The trace is found in ``ctx.trace_dir``
where the harness gives one, else in ``harness.TRACE_DIR``, where
``bench/run.py`` writes it.  A trace whose ``bench.window`` does not match
the run's own reduction is another run's and reads as nothing, as does a
trace that holds no program spans (a program without them).
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

from bench import trace as tracing

PREFIX = "repro."
PARK = "repro.engine.park"

Interval = Tuple[float, float]


@dataclass
class Spans:
    """The ``repro.*`` events of one traced window."""

    lo: float                       # the window, ns on the trace's clock
    hi: float
    events: List[tracing.DeviceOp]  # every repro.* event overlapping it

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _clipped(self, keep: Callable[[str], bool]) -> List[Interval]:
        return tracing.clip([(e.start, e.end) for e in self.events
                             if keep(e.name)], self.lo, self.hi)

    def span_s(self, name: str) -> float:
        """Seconds of the events named ``name``, clipped to the window."""
        return sum(e - s for s, e in self._clipped(lambda n: n == name)) / 1e9

    def count(self, name: str) -> int:
        return sum(e.name == name for e in self.events)

    def union_s(self, keep: Callable[[str], bool]) -> float:
        """Seconds of the window under at least one kept event."""
        busy = tracing.union(self._clipped(keep))
        return sum(e - s for s, e in busy) / 1e9

    def names(self) -> List[str]:
        return sorted({e.name for e in self.events})

    def covered_s(self, intervals: List[Interval]) -> float:
        """Seconds of ``intervals`` that lie under a program span."""
        spans = tracing.union(self._clipped(lambda _n: True))
        starts = [a for a, _b in spans]
        total = 0.0
        for s, e in intervals:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(spans) and spans[i][0] < e:
                a, b = spans[i]
                total += max(0.0, min(e, b) - max(s, a))
                i += 1
        return total / 1e9

    def label(self, gap: Interval, notes: List[tracing.DeviceOp]) -> str:
        """The program span that overlaps ``gap`` the most, else the
        ``bench.*`` annotation that does, else ``unattributed``."""
        for pool in (self.events, notes):
            best, best_overlap = None, 0.0
            for n in pool:
                overlap = min(gap[1], n.end) - max(gap[0], n.start)
                if overlap > best_overlap:
                    best, best_overlap = n.name, overlap
            if best is not None:
                return best
        return "unattributed"


def of_trace(pd) -> Spans:
    """The program spans of a loaded trace, in its ``bench.window``."""
    marks = [n for n in tracing.host_annotations(pd)
             if n.name == tracing.WINDOW]
    if not marks:
        raise ValueError(f"trace has no {tracing.WINDOW!r} annotation")
    lo, hi = marks[0].start, marks[0].end
    events = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    op = tracing._op(e)
                    if op.end > lo and op.start < hi:
                        events.append(op)
    return Spans(lo, hi, events)


def read(ctx) -> Optional[Spans]:
    """The program spans of this run's traced window, or None.  The first
    reader of a run loads them and leaves them on ``ctx`` for the others."""
    if not hasattr(ctx, "program_spans"):
        ctx.program_spans = _load(ctx)
    return ctx.program_spans


def _load(ctx) -> Optional[Spans]:
    r = getattr(ctx, "trace", None)
    if r is None:
        return None
    trace_dir = getattr(ctx, "trace_dir", None)
    if trace_dir is None:
        from bench import harness

        trace_dir = harness.TRACE_DIR
    try:
        spans = of_trace(tracing.load(str(trace_dir)))
    except (FileNotFoundError, ValueError):
        return None
    if abs(spans.window_s - r.window_s) > 1e-9 or not spans.events:
        return None
    return spans


def engine_busy(name: str) -> bool:
    """The engine thread's work: its round phases and the batcher's spans,
    without the park."""
    return (name.startswith(("repro.engine.", "repro.batcher."))
            and name != PARK)


def ns_per_token(ctx, name: str, counter: str) -> Optional[float]:
    """Nanoseconds of span ``name`` in the window per token of ``counter``
    (a ``ServerTelemetry`` count) over the same telemetry window as
    ``lanes_per_dispatch``."""
    spans = read(ctx)
    c1 = getattr(ctx.tel1, counter, None)
    c0 = getattr(ctx.tel0, counter, None)
    if spans is None or c1 is None or c0 is None or c1 <= c0:
        return None
    seconds = spans.span_s(name)
    if seconds <= 0:
        return None
    return seconds * 1e9 / (c1 - c0)
