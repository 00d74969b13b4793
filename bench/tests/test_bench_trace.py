"""The profiler-trace reduction: idle share, kernel time by name, idle gaps."""

import time
from types import SimpleNamespace

import pytest

from bench import trace as tr


def _ev(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=float(start),
                           duration_ns=float(dur), stats=list(stats.items()))


def _pd(device_events, host_events):
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=device_events),
        SimpleNamespace(name="XLA Modules", events=[_ev("jit_step", 0, 10**9)]),
    ])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=host_events),
    ])
    return SimpleNamespace(planes=[host, dev])


def test_reduce_synthetic_device_plane():
    # window [100, 1100) ns; ops cover [100,300) + [250,400) + [900,1000)
    pd = _pd(
        [_ev("stream_fused", 100, 200), _ev("fusion.3", 250, 150),
         _ev("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 900, 100),
         _ev("stream_fused", 2000, 50)],  # outside the window
        [_ev("bench.window", 100, 1000), _ev("bench.wait", 400, 450),
         _ev("bench.submit", 1000, 100), _ev("other", 0, 10)],
    )
    r = tr.reduce(pd)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(400e-9)          # union, not the sum
    assert r.devices == 1
    assert r.time_of("stream_fused") == pytest.approx(200e-9)
    assert r.time_of("f32[8]") == 0.0        # names, not operand shapes
    assert r.op_s == pytest.approx(
        {"stream_fused": 200e-9, "fusion.3": 150e-9, "copy.2": 100e-9})
    assert [o.shape for o in r.ops if o.name == "copy.2"] == [(8,)]
    assert sorted(r.idle_gaps, key=lambda g: -g[1]) == [
        ("bench.wait", pytest.approx(500e-9)),
        ("bench.submit", pytest.approx(100e-9)),
    ]
    b = r.breakdown(top=2)
    assert [n for n, _ in b["device_ops"]] == ["stream_fused", "fusion.3"]
    assert b["idle_gaps"][0][0] == "bench.wait"


def test_reduce_counts_each_device_and_averages_busy():
    pd = _pd([_ev("k", 0, 50)], [_ev("bench.window", 0, 100)])
    pd.planes.append(SimpleNamespace(name="/device:TPU:1", lines=[
        SimpleNamespace(name="XLA Ops", events=[_ev("k", 0, 100)])]))
    r = tr.reduce(pd)
    assert r.devices == 2
    assert r.busy_s == pytest.approx(75e-9)
    assert ("unattributed", pytest.approx(50e-9)) in r.idle_gaps


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        tr.reduce(_pd([], []))


def test_interval_helpers():
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_reduce_a_recorded_trace(tmp_path):
    """A real profiler trace of a few jitted calls on this backend, with
    the benchmark's annotations around them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    with tr.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
            f(x).block_until_ready()
    r = tr.reduce(tr.load(str(tmp_path)))
    assert 0.05 <= r.window_s < 5.0
    assert 0.0 < r.busy_s < r.window_s
    assert r.time_of("dot") > 0.0
    assert sum(r.op_s.values()) >= r.busy_s * 0.99
    longest = max(r.idle_gaps, key=lambda g: g[1])
    assert longest[0] == "bench.wait"
    assert longest[1] >= 0.045


def test_roofline_reader_counts_the_calls_tokens():
    from bench.metrics import stream_fused_roofline

    text = ("%vmap_stream_fused_.1 = f32[32,1,128,128]{3,2,1,0} custom-call("
            "f32[32,1,128,128]{3,2,1,0} %x), custom_call_target=\"tpu\"")
    reshape = ("%reshape.1 = f32[32,4,4096]{2,1,0} reshape(f32[32,1,128,128]"
               " %vmap_stream_fused_.1)")
    pd = _pd([_ev(text, 0, 8000), _ev(reshape, 8000, 4000),
              _ev(text, 20000, 8000)], [_ev("bench.window", 0, 10**6)])
    r = tr.reduce(pd)
    assert [c.name for c in r.calls_of("stream_fused")] == [
        "vmap_stream_fused_.1"] * 2
    assert r.op_s["reshape.1"] == pytest.approx(4e-6)
    ctx = SimpleNamespace(
        trace=r, log=lambda _m: None,
        peaks={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
        config={"kernel": {"name": "stream_fused", "in_wires": 1,
                           "out_wires": 1, "ops": [["matmul8"]]}},
    )
    tokens = 2 * 32 * 128 * 128
    want = 100.0 * (tokens * 8 / 819e9) / 16e-6
    assert stream_fused_roofline.read(ctx) == pytest.approx(want)
    ctx.trace = None
    assert stream_fused_roofline.read(ctx) is None
