"""One run of one cell: build, warm, drive the window, check, measure.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/<config>.json``,
whose ``builder`` names ``bench/networks/<builder>.py`` and
``bench/reference/<builder>.py``) and its traffic mix
(``bench/traffic/<traffic>.json``, read by ``bench/drive.py``); each
per-layer metric is read by ``bench/metrics/<name up to its first dot>.py``.
"""

from __future__ import annotations

import gc
import importlib
import json
import resource
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import drive, trace as tracing

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: Optional[Dict] = None) -> Cell:
    spec = spec or read_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"({[w['name'] for w in spec['workloads']]})")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=entry["chips"],
        config=read_json(ROOT / cfg_entry["file"]),
        mix=read_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def peaks(device_kind: str) -> Dict:
    """Peak rates of one chip; an unknown device is an error."""
    table = read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def builder(config: Dict):
    return importlib.import_module(f"bench.networks.{config['builder']}")


def reference(config: Dict):
    return importlib.import_module(f"bench.reference.{config['builder']}")


class CompileCounter:
    """Counts backend compilations and traces while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self.counts:
            self.counts[event] += 1

    def summary(self) -> Dict[str, int]:
        return {"backend_compiles": self.counts[self.EVENTS[0]],
                "traces": self.counts[self.EVENTS[1]]}


class GcPauses:
    """Python garbage-collector passes while armed: (generation, start,
    seconds), start on the ``perf_counter`` clock."""

    def __init__(self):
        self.armed = False
        self.passes: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.passes.append((info["generation"], self._t,
                                time.perf_counter() - self._t))

    def summary(self) -> Dict[str, float]:
        full = [s for g, _t, s in self.passes if g == 2]
        return {"passes": len(self.passes), "full_passes": len(full),
                "seconds": sum(s for _g, _t, s in self.passes),
                "longest_s": max((s for _g, _t, s in self.passes),
                                 default=0.0)}

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)


def build_program(config: Dict, platform: Optional[str] = None):
    """Compile the configuration's network for the device, as served."""
    import repro

    net, _ = builder(config).build(config)
    prog = repro.compile(net, backend="device", block=config["block"],
                         megastep=config["megastep"])
    for pid, dp in prog.device_programs().items():
        if platform is not None and (dp.device is None
                                     or dp.device.platform != platform):
            raise RuntimeError(f"{pid}: bound to {dp.device}, not {platform}")
    return prog


def new_server(prog, config: Dict):
    """A server over ``prog`` with every batch width up to ``max_batch``
    compiled (the jit cache of the program's own ``batched_megastep``), so
    that no width compiles inside the window."""
    server = prog.serve(max_batch=config["max_batch"],
                        admission_depth=config["admission_depth"])
    for batcher in server._batchers.values():
        for width in range(1, config["max_batch"] + 1):
            batcher.prepare(width)
    return server


def drive_window(server, cell: Cell, seed: int, seconds: float,
                 traced: bool, compiles: Optional[CompileCounter] = None,
                 t_start: Optional[float] = None, gen=None,
                 trace_dir: Path = TRACE_DIR):
    """Lead-in, window and drain of one run.  Returns the window, the
    telemetry at its two ends and set-up seconds (from ``t_start`` to the
    window's start).  Counts compilations and garbage-collector passes
    inside the window into the window's notes."""
    gen = gen or drive.make(cell.mix, cell.config, seed, seconds)
    compiles = compiles or CompileCounter()
    pauses = GcPauses()
    server.start()
    gen.lead_in(server)
    setup_s = None if t_start is None else time.perf_counter() - t_start
    tel0 = server.telemetry.lifetime()
    compiles.armed = pauses.armed = True
    try:
        if traced:
            with tracing.capture(str(trace_dir)):
                win = gen.window(server, seconds)
        else:
            win = gen.window(server, seconds)
    finally:
        compiles.armed = pauses.armed = False
        pauses.close()
    tel1 = server.telemetry.lifetime()
    gen.finish(server, win)
    win.gc_passes = pauses.passes
    win.notes.update({f"compiles_in_window.{k}": v
                      for k, v in compiles.summary().items()})
    win.notes.update({f"gc_in_window.{k}": v
                      for k, v in pauses.summary().items()})
    return win, tel0, tel1, setup_s


def compare(config: Dict, checked: List[drive.Checked],
            produce: Callable) -> Dict[str, Dict]:
    """The numbers compared, each with its limit.  ``produce(session,
    inputs, port, n)`` gives the first ``n`` outputs under test on ``port``
    (the served ones, or the control's)."""
    ref = reference(config)
    gaps = {p: 0.0 for p in config["egress"]}
    failed = 0
    for c in checked:
        inputs = c.inputs()
        bad = c.session.error is not None
        outs = {}
        for port in config["egress"]:
            n = len(c.session.results[port])
            if n > len(inputs) or (c.complete and n != len(inputs)) \
                    or (not c.complete and n == 0):
                bad = True
            outs[port] = n
        failed += bad
        if bad:
            continue
        want = ref.reference(config, inputs[:max(outs.values())])
        for port, n in outs.items():
            if n:
                got = produce(c.session, inputs, port, n)
                gap = float(np.max(np.abs(got - want[port][:n])))
                gaps[port] = max(gaps[port], gap if gap == gap else np.inf)
    checks = {f"max_gap.{p}": {"value": g,
                               "limit": config["checks"][f"max_gap.{p}"]}
              for p, g in gaps.items()}
    checks["failed_sessions"] = {"value": failed, "limit": 0}
    return checks


def served(session, _inputs, port: str, n: int) -> np.ndarray:
    return np.asarray(session.results[port][:n], np.float64)


def controlled(config: Dict) -> Callable:
    """The control put in the program's place: the reference one precision
    step lower, over the same inputs and output counts."""
    ctl = reference(config).control
    memo = {}

    def produce(session, inputs, port, n):
        key = id(session)
        if key not in memo:
            memo.clear()
            memo[key] = ctl(config, inputs)
        return memo[key][port][:n]

    return produce


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def per_layer(cell: Cell, ctx) -> Dict[str, Dict]:
    """Each per-layer metric's reader; a reader that finds nothing returns
    None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = importlib.import_module(
            f"bench.metrics.{m['name'].split('.')[0]}"
        )
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, platform: Optional[str],
        trace_dir: Path = TRACE_DIR) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    dev = jax.devices()[0]
    t = time.perf_counter()
    prog = build_program(cell.config, platform)
    t_built = time.perf_counter()
    server = new_server(prog, cell.config)
    log(f"set-up: {t - t_start!r} s to JAX's devices, {t_built - t!r} s to "
        f"compile the network, {time.perf_counter() - t_built!r} s to warm "
        f"{cell.config['max_batch']} batch widths")
    win, tel0, tel1, setup_s = drive_window(
        server, cell, seed, seconds, traced, t_start=t_start,
        trace_dir=trace_dir,
    )
    server.stop()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {win.seconds!r} s, setup {setup_s!r} s")
    for k, v in win.notes.items():
        log(f"window note: {k} = {v!r}")
    log(f"telemetry over the window: dispatches "
        f"{tel1.device_dispatches - tel0.device_dispatches}, lanes "
        f"{tel1.device_lanes - tel0.device_lanes}, width "
        f"{tel1.device_width - tel0.device_width}")

    reduction = None
    if traced:
        reduction = tracing.reduce(tracing.load(str(trace_dir)))
    del prog, server
    gc.collect()

    t_check = time.perf_counter()
    checks = compare(cell.config, win.checked, served)
    correct = passed(checks)
    log(f"reference check: {time.perf_counter() - t_check!r} s")
    log(f"host: peak resident set "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} bytes")
    result: Dict = {
        "correct": correct,
        "attempted": len(win.checked),
        "failed": checks["failed_sessions"]["value"],
        "metrics": {},
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak,
        },
    }
    if traced:
        ctx = SimpleNamespace(
            trace=reduction, tel0=tel0, tel1=tel1, config=cell.config,
            peaks=peaks(dev.device_kind) if platform else None, log=log,
        )
        result["metrics"] = per_layer(cell, ctx)
        result["device"]["busy_s"] = reduction.busy_s
        result["device"]["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    else:
        values = dict(win.values, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
