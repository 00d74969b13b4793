"""Engine-round spans: the ``span`` helper, the serving engine's phase spans
and the batcher's launch/retire spans in a recorder and in the JAX
profiler's trace, exact replay of what they carry, and the idle backoff
that parks the engine between device rounds."""

import glob
import os

import jax
import pytest

import repro
from repro.apps.streams import NETWORKS
from repro.observability import (
    TraceRecorder,
    chrome_trace,
    snapshot_from_trace,
    span,
)
from repro.runtime.scheduler import AdaptiveBackoff
from helpers import drain_source

ENGINE_PHASES = {"pump", "host", "order", "egress", "complete"}


def _x_events(rec):
    return [e for e in rec.events() if e[0] == "X"]


# ---------------------------------------------------------------------------
# The helper
# ---------------------------------------------------------------------------


def test_span_records_name_category_and_args():
    rec = TraceRecorder()
    with span(rec, "batch:p0", "batcher", "retire", cat="device",
              round=3) as sp:
        sp.args.update(tokens_out=8, lanes=2, time_ns=5)
    with span(rec, "engine", "engine", "pump", round=3):
        pass
    (retire, pump) = _x_events(rec)
    assert retire[:4] == ("X", "batch:p0", "retire", "device")
    assert retire[4] == sp.t0_ns and retire[5] == sp.dur_ns > 0
    assert retire[6] == {"round": 3, "tokens_out": 8, "lanes": 2,
                         "time_ns": 5}
    assert pump[1:4] == ("engine", "pump", "engine")  # cat = layer
    assert pump[6] == {"round": 3}


def test_span_without_recorder_or_discarded_records_nothing():
    with span(None, "engine", "engine", "pump", round=1) as sp:
        sp.args["tokens"] = 4
    assert sp.dur_ns > 0
    rec = TraceRecorder()
    with span(rec, "session:0", "actor", "filter") as sp:
        sp.discard()
    with pytest.raises(RuntimeError):
        with span(rec, "engine", "engine", "order", round=2):
            raise RuntimeError("a failing phase still closes its span")
    assert [e[2] for e in _x_events(rec)] == ["order"]


def test_span_lands_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with span(None, "engine", "engine", "egress", round=7):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "repro.engine.egress" in names


# ---------------------------------------------------------------------------
# The sites in a served run
# ---------------------------------------------------------------------------


def _serve_traced(name, n, block, mode=True, xcf=None, sessions=2):
    net, _ = (NETWORKS[name](n=n) if name == "FIR32"
              else NETWORKS[name](n))
    prog = repro.compile(net, backend="device", block=block)
    if xcf is not None:
        prog = prog.repartition(xcf)
    stream = drain_source(prog.graph)
    with prog.serve(trace=True, batching=mode) as server:
        ss = [server.open_session() for _ in range(sessions)]
        for i in range(0, len(stream), 64):
            for s in ss:
                s.submit(stream[i:i + 64])
        for s in ss:
            s.close()
        assert server.drain(timeout=120)
        life = server.telemetry.lifetime()
        events = server.recorder.events()
        payload = server.trace()
    return life, events, payload


@pytest.mark.parametrize("mode", [True, False])
def test_launch_spans_replace_the_dispatch_instant(mode):
    life, events, payload = _serve_traced("IDCT8", 512, 64, mode=mode)
    device = [e for e in events if e[3] == "device"]
    assert {e[2] for e in device} == {"launch", "retire"}
    assert all(e[0] == "X" for e in device)   # no instants left
    launches = [e for e in device if e[2] == "launch"]
    assert len(launches) == life.device_dispatches > 0
    assert sum(e[6]["lanes"] for e in launches) == life.device_lanes
    assert sum(e[6]["width"] for e in launches) == life.device_width
    assert sum(e[6]["tokens_in"] for e in launches) == life.device_tokens_in
    retires = [e for e in device if e[2] == "retire"]
    assert sum(e[6]["tokens_out"] for e in retires) == life.device_tokens_out
    assert sum(e[6]["time_ns"] for e in retires) == life.device_time_ns
    assert all(set(e[6]) == {"round", "tokens_out", "lanes", "time_ns"}
               for e in retires)
    snap = snapshot_from_trace(payload)
    for f in ("device_dispatches", "device_lanes", "device_width",
              "lanes_peak", "device_tokens_in", "device_tokens_out",
              "device_time_ns"):
        assert getattr(snap, f) == getattr(life, f), f


def test_engine_phase_spans_carry_the_round_and_replay_tokens_pumped():
    life, events, payload = _serve_traced("IDCT8", 512, 64)
    engine = [e for e in events if e[0] == "X" and e[1] == "engine"]
    assert ENGINE_PHASES <= {e[2] for e in engine}
    assert {e[2] for e in engine} <= ENGINE_PHASES | {"park"}
    assert all(e[3] == "engine" and isinstance(e[6]["round"], int)
               for e in engine)
    # flat: one phase's span ends before the next one of its round starts
    by_round = {}
    for e in engine:
        by_round.setdefault(e[6]["round"], []).append(e)
    for spans in by_round.values():
        spans.sort(key=lambda e: e[4])
        for a, b in zip(spans, spans[1:]):
            assert a[4] + a[5] <= b[4]
    pumped = sum(e[6].get("tokens", 0) for e in engine if e[2] == "pump")
    assert pumped == life.tokens_pumped == life.tokens_submitted
    assert snapshot_from_trace(payload).tokens_pumped == life.tokens_pumped
    assert any(ev.get("name") == "pump" and ev.get("ph") == "X"
               for ev in payload["traceEvents"])


def test_host_actor_spans_match_telemetry():
    """Host actors in a served mixed placement: one span per productive
    invoke, the same key, fires and nanoseconds as telemetry."""
    from repro.core.xcf import make_xcf

    xcf = make_xcf("IDCT8", {"source": "t0", "descale": "t0",
                             "idct": "accel", "clip": "t0", "sink": "t0"})
    life, events, payload = _serve_traced("IDCT8", 512, 64, xcf=xcf)
    actors = [e for e in events if e[0] == "X" and e[3] == "actor"]
    assert actors and all(e[1].startswith("session:") for e in actors)
    fires, time_ns = {}, {}
    for e in actors:
        assert e[6]["fires"] > 0
        fires[e[2]] = fires.get(e[2], 0) + e[6]["fires"]
        time_ns[e[2]] = time_ns.get(e[2], 0) + e[5]
    assert fires == life.actor_fires
    assert time_ns == life.actor_time_ns
    snap = snapshot_from_trace(payload)
    assert snap.actor_fires == life.actor_fires


def test_replay_skips_a_launch_that_dispatched_nothing():
    rec = TraceRecorder()
    with pytest.raises(RuntimeError):
        with span(rec, "batch:p", "batcher", "launch", cat="device",
                  round=1):
            raise RuntimeError("the launch failed before it dispatched")
    with span(rec, "batch:p", "batcher", "launch", cat="device",
              round=2) as sp:
        sp.args.update(lanes=2, tokens_in=8, width=2)
    snap = snapshot_from_trace(chrome_trace(rec))
    assert (snap.device_dispatches, snap.device_lanes,
            snap.device_tokens_in) == (1, 2, 8)


# ---------------------------------------------------------------------------
# The park's backoff
# ---------------------------------------------------------------------------


def test_adaptive_backoff_holds_at_cap_without_overflow():
    b = AdaptiveBackoff(first=20e-6, cap=1e-3)
    seq = [b.next_timeout() for _ in range(5000)]
    assert seq[:2] == [0.0, 0.0]
    assert seq[2] == pytest.approx(20e-6)
    assert max(seq) == 1e-3
    assert all(t == 1e-3 for t in seq[8:])   # 20us * 2**6 > 1ms
    b.reset()
    assert b.next_timeout() == 0.0
