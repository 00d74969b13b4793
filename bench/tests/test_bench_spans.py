"""The program's spans in a profiler trace: window clipping, the engine's
busy union, idle-gap labels, and the five readers of the engine's phases
on a recorded serve at test size."""

import math
import time
from types import SimpleNamespace

import pytest

from bench import harness, spans as program_spans, trace as tr
from bench.metrics import (
    egress_ns_per_token,
    engine_busy_pct,
    launch_ns_per_token,
    pump_ns_per_token,
    retire_ns_per_token,
)
from bench.tests.test_bench_cells import tiny

READERS = {
    "engine_busy_pct.tput": engine_busy_pct,
    "pump_ns_per_token.tput": pump_ns_per_token,
    "launch_ns_per_token.tput": launch_ns_per_token,
    "retire_ns_per_token.tput": retire_ns_per_token,
    "egress_ns_per_token.tput": egress_ns_per_token,
}


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=float(start),
                           duration_ns=float(dur), stats=[])


def _pd(device_events, host_events, engine_events=()):
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=device_events)])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="bench", events=host_events),
        SimpleNamespace(name="engine", events=list(engine_events))])
    return SimpleNamespace(planes=[host, dev])


# window [0, 1000) ns; device busy [100, 200) and [600, 700)
PD = _pd(
    [_ev("stream_fused", 100, 100), _ev("stream_fused", 600, 100)],
    [_ev("bench.window", 0, 1000), _ev("bench.wait", 150, 850)],
    [_ev("repro.engine.pump", -50, 100),          # clipped to [0, 50)
     _ev("repro.batcher.launch", 60, 140),
     _ev("repro.batcher.retire", 250, 200),
     _ev("repro.engine.park", 450, 100),
     _ev("repro.engine.egress", 950, 100),        # clipped to [950, 1000)
     _ev("repro.engine.pump", 2000, 10)],         # outside the window
)


def test_span_seconds_clip_to_the_window():
    s = program_spans.of_trace(PD)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.span_s("repro.engine.pump") == pytest.approx(50e-9)
    assert s.count("repro.engine.pump") == 1
    assert s.span_s("repro.engine.egress") == pytest.approx(50e-9)
    assert s.span_s("repro.engine.order") == 0.0
    # the engine's work: everything but the park, as a union
    assert s.union_s(program_spans.engine_busy) == pytest.approx(440e-9)


def test_idle_gaps_take_the_program_span_over_them():
    r = tr.reduce(PD)
    s = program_spans.of_trace(PD)
    gaps = {g: s.label(g, r.notes) for g in r.idle}
    # [200, 600) overlaps bench.wait for 400 ns, retire for 200 ns: the
    # program's span names it
    assert gaps[(200.0, 600.0)] == "repro.batcher.retire"
    assert gaps[(0.0, 100.0)] == "repro.engine.pump"   # 50 ns, launch 40
    assert gaps[(700.0, 1000.0)] == "repro.engine.egress"
    # a gap under no program span keeps its bench.* label
    bare = program_spans.Spans(0.0, 1000.0, [])
    assert bare.label((200.0, 600.0), r.notes) == "bench.wait"
    assert bare.label((-90.0, -10.0), r.notes) == "unattributed"
    # idle time under a program span: 90 of [0,100), 300 of [200,600)
    # with the park, 50 of [700,1000)
    assert s.covered_s(r.idle) == pytest.approx(440e-9)


def _ctx(trace_dir, reduction, tel0, tel1):
    return SimpleNamespace(trace=reduction, trace_dir=trace_dir, tel0=tel0,
                           tel1=tel1, log=lambda _m: None)


def _capture(trace_dir, with_span):
    import jax

    from repro.observability import span

    with tr.capture(str(trace_dir)):
        with jax.profiler.TraceAnnotation("bench.window"):
            jax.numpy.ones(8).block_until_ready()
            if with_span:
                with span(None, "engine", "engine", "pump", round=1):
                    time.sleep(0.01)
    return tr.reduce(tr.load(str(trace_dir)))


def test_readers_read_nothing_without_program_spans(tmp_path):
    """A program without the spans, a telemetry without the pump counter,
    another run's trace or no trace reads as nothing, and nothing raises."""
    tel = SimpleNamespace(tokens_pumped=0, device_tokens_in=0,
                          device_tokens_out=0, tokens_delivered=0)
    more = SimpleNamespace(tokens_pumped=10, device_tokens_in=10,
                           device_tokens_out=10, tokens_delivered=10)
    bare = _capture(tmp_path / "bare", False)
    for ctx in (_ctx(tmp_path / "bare", bare, tel, more),
                _ctx(tmp_path / "bare", None, tel, more),
                _ctx(tmp_path / "empty", bare, tel, more)):
        assert all(m.read(ctx) is None for m in READERS.values())
    r = _capture(tmp_path / "spans", True)
    ctx = _ctx(tmp_path / "spans", r, tel, more)
    assert pump_ns_per_token.read(ctx) == pytest.approx(
        program_spans.read(ctx).span_s("repro.engine.pump") * 1e9 / 10)
    assert engine_busy_pct.read(ctx) > 0
    old = SimpleNamespace(tokens_delivered=0)   # no tokens_pumped counter
    assert pump_ns_per_token.read(_ctx(tmp_path / "spans", r, old, more)) \
        is None
    other = SimpleNamespace(window_s=r.window_s + 1e-6, idle=[], notes=[])
    assert engine_busy_pct.read(_ctx(tmp_path / "spans", other, tel, more)) \
        is None


def test_five_readers_on_a_recorded_serve(tmp_path, monkeypatch):
    """A traced run of the served IDCT8 cell at test size: the trace holds
    the engine's and the batcher's spans and each reader reads a finite
    positive number from it, through the harness's own result line."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    cell = tiny("idct8.serve.streams64")
    result = harness.run(cell, 2 ** 31 + 5, 1.0, True, time.perf_counter(),
                         platform=None, trace_dir=tmp_path)
    assert result["correct"] is True
    names = program_spans.of_trace(tr.load(str(tmp_path))).names()
    for phase in ("repro.engine.pump", "repro.engine.host",
                  "repro.engine.order", "repro.engine.egress",
                  "repro.engine.complete", "repro.batcher.launch",
                  "repro.batcher.retire"):
        assert phase in names
    for name in READERS:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, name
    assert result["metrics"]["engine_busy_pct.tput"]["value"] <= 100.0
