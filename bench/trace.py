"""Profiler trace capture and its reduction to device busy time, kernel
time and idle gaps.

The JAX profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``;
``jax.profiler.ProfileData`` reads it.  On a TPU the device's operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  On the
CPU backend, which has no device plane, they are the host events that carry
an ``hlo_op`` stat.  The window is the benchmark's own ``bench.window``
annotation; the host's other ``bench.*`` annotations label the idle gaps.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(log_dir: str):
    """Record a profiler trace of the block into ``log_dir`` (emptied
    first).  The Python tracer stays off: it would slow the host threads
    the benchmark measures."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str):
    """The newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


@dataclass
class DeviceOp:
    name: str     # the HLO instruction's name (``vmap_stream_fused_.1``)
    start: float  # ns, on the trace's clock
    end: float
    text: str = ""  # the event's full name: on a TPU, the HLO instruction

    @property
    def shape(self) -> Tuple[int, ...]:
        """Dimensions of the instruction's (first) result."""
        m = _RESULT.search(self.text)
        return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


# ``%name = f32[32,1,128,128]{...} custom-call(...)``
_RESULT = re.compile(r"= \(?\w+\[([\d,]*)\]")


def _op(ev) -> DeviceOp:
    name = ev.name.split(" = ", 1)[0].lstrip("%")
    return DeviceOp(name, ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)


def _has_hlo_op(ev) -> bool:
    try:
        return "hlo_op" in dict(ev.stats)
    except (TypeError, ValueError):
        return False


def device_ops(pd) -> Dict[str, List[DeviceOp]]:
    """Device operations per device plane (one ``cpu`` pseudo-device where
    the trace has no device plane)."""
    out: Dict[str, List[DeviceOp]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            out.setdefault(plane.name, []).extend(
                _op(e) for e in line.events
            )
    if out:
        return out
    ops = [_op(e) for plane in pd.planes for line in plane.lines
           for e in line.events if _has_hlo_op(e)]
    return {"cpu": ops} if ops else {}


def host_annotations(pd) -> List[DeviceOp]:
    """The benchmark's own ``bench.*`` annotations, on any host line."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    out.append(_op(e))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals, sorted by start."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(gap: Interval, notes: List[DeviceOp]) -> str:
    """The annotation that overlaps the gap the most, else
    ``unattributed``."""
    best, best_overlap = "unattributed", 0.0
    for n in notes:
        overlap = min(gap[1], n.end) - max(gap[0], n.start)
        if overlap > best_overlap:
            best, best_overlap = n.name, overlap
    return best


@dataclass
class Reduction:
    """One traced window: seconds, device busy seconds (averaged over the
    devices), device time per op name, and idle gaps labelled by what the
    benchmark's host thread was doing."""

    window_s: float
    busy_s: float
    devices: int
    op_s: Dict[str, float]
    ops: List[DeviceOp]
    idle: List[Interval]        # gaps between device ops, every device
    notes: List[DeviceOp]       # the host's bench.* annotations

    @property
    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap as (label, seconds)."""
        return [(_label(g, self.notes), (g[1] - g[0]) / 1e9)
                for g in self.idle]

    def calls_of(self, kernel: str) -> List[DeviceOp]:
        """The device ops whose instruction name holds ``kernel`` (a Pallas
        call's ``name``, under ``vmap`` too)."""
        return [o for o in self.ops if kernel in o.name]

    def time_of(self, kernel: str) -> float:
        """Seconds of device time of ``kernel``'s calls."""
        return sum(o.end - o.start for o in self.calls_of(kernel)) / 1e9

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[_label(g, self.notes), (g[1] - g[0]) / 1e9]
                              for g in idle]}


def reduce(pd, window: Optional[Interval] = None) -> Reduction:
    """Reduce a trace to one window: ``window`` (ns on the trace's clock)
    or, by default, the ``bench.window`` annotation's extent."""
    notes = host_annotations(pd)
    if window is None:
        marks = [n for n in notes if n.name == WINDOW]
        if not marks:
            raise ValueError(f"trace has no {WINDOW!r} annotation")
        window = (marks[0].start, marks[0].end)
    lo, hi = window
    per_device = device_ops(pd)
    ops: List[DeviceOp] = []
    busy_total = 0.0
    idle: List[Interval] = []
    for dev_ops in per_device.values():
        inside = [o for o in dev_ops if o.end > lo and o.start < hi]
        ops.extend(inside)
        busy = union(clip([(o.start, o.end) for o in inside], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        idle.extend(gaps(busy, lo, hi))
    n_dev = max(len(per_device), 1)
    op_s: Dict[str, float] = {}
    for o in ops:
        d = (min(o.end, hi) - max(o.start, lo)) / 1e9
        op_s[o.name] = op_s.get(o.name, 0.0) + d
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n_dev / 1e9,
        devices=len(per_device),
        op_s=op_s,
        ops=ops,
        idle=idle,
        notes=[n for n in notes if n.name != WINDOW],
    )
