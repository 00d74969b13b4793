"""The fir29_lowpass configuration: its network's topology, its taps (the
CMSIS-DSP example's fir1 design), its reference and control, and the work
count and readers of its two per-layer metrics."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.metrics import blocks_per_lane, stateful_fused_roofline
from bench.reference import fir29_lowpass as ref
from bench.trace import DeviceOp
from bench.work import stateful_fused

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "fir29_lowpass.json").read_text())
# firCoeffs32 of CMSIS-DSP's arm_fir_example_f32.c, as printed there
CMSIS = [-0.0018225230, -0.0015879294, +0.0000000000, +0.0036977508,
         +0.0080754303, +0.0085302217, -0.0000000000, -0.0173976984,
         -0.0341458607, -0.0333591565, +0.0000000000, +0.0676308395,
         +0.1522061835, +0.2229246956, +0.2504960933, +0.2229246956,
         +0.1522061835, +0.0676308395, +0.0000000000, -0.0333591565,
         -0.0341458607, -0.0173976984, -0.0000000000, +0.0085302217,
         +0.0080754303, +0.0036977508, +0.0000000000, -0.0015879294,
         -0.0018225230]


def _samples(n, seed):
    lo, hi = CONFIG["value_range"]
    return np.random.default_rng(seed).integers(lo, hi + 1, n).astype(float)


def test_topology_is_the_systolic_chain():
    net, got = harness.builder(CONFIG).build(CONFIG, [1.0] * 64)
    graph = net.graph()
    taps = [f"tap{i}" for i in range(29)]
    assert graph.name == CONFIG["network"] and set(got) == {"sink", "xsink"}
    assert set(graph.actors) == {"source", "seed", "sink", "xsink", *taps}
    for i, name in enumerate(taps):
        actor = graph.actors[name]
        assert actor.stream_op == ("fir_tap", CONFIG["taps"][i])
        assert actor.initial_state == {"z": 0.0}
    chain = ["seed", *taps]
    want = {("source", "OUT", "seed", "IN"),
            (taps[-1], "AOUT", "sink", "IN"),
            (taps[-1], "XOUT", "xsink", "IN")}
    for a, b in zip(chain, chain[1:]):
        want |= {(a, "XOUT", b, "XIN"), (a, "AOUT", b, "AIN")}
    assert {c.key for c in graph.channels} == want


def test_taps_are_fir1_and_the_cmsis_coefficients():
    taps = np.asarray(CONFIG["taps"])
    np.testing.assert_allclose(taps, ref.fir1_taps(28, 6 / 24), rtol=0,
                               atol=1e-15)
    assert taps.sum() == pytest.approx(1.0, abs=1e-15)
    np.testing.assert_allclose(taps, CMSIS, rtol=0, atol=6e-11)
    np.testing.assert_allclose(taps, taps[::-1], atol=1e-15)  # linear phase


def test_reference_equals_the_host_run():
    import repro

    x = _samples(512, 7)
    net, got = harness.builder(CONFIG).build(CONFIG, x)
    repro.compile(net, backend="host").run()
    want = ref.reference(CONFIG, x)
    np.testing.assert_allclose(np.asarray(got["sink"]), want["sink"],
                               rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(got["xsink"]), want["xsink"])


def test_xsink_is_the_input_delayed_by_29():
    x = _samples(100, 3)
    out = ref.reference(CONFIG, x)["xsink"]
    np.testing.assert_array_equal(out, np.r_[np.zeros(29), x[:71]])
    impulse = np.zeros(40)
    impulse[0] = 1.0
    np.testing.assert_allclose(ref.reference(CONFIG, impulse)["sink"][:29],
                               CONFIG["taps"])


def test_limit_sits_between_the_readings():
    """float32 gaps near 1e-2 at 16-bit samples, bfloat16 control gaps in
    the hundreds; the limit stays below the 1.0 a single altered token
    adds."""
    limit = CONFIG["checks"]["max_gap.sink"]
    assert limit < 1.0 and CONFIG["checks"]["max_gap.xsink"] == 0.0
    h = np.asarray(CONFIG["taps"], np.float32)
    for seed in (11, 12):
        x = _samples(48_000, seed)
        want = ref.reference(CONFIG, x)
        xd = x.astype(np.float32)
        acc = np.zeros_like(xd)
        for c in h:
            acc = acc + c * xd
            xd = np.r_[np.float32(0.0), xd[:-1]]
        assert np.max(np.abs(acc - want["sink"])) < limit / 4
        ctl = ref.control(CONFIG, x)
        assert np.max(np.abs(ctl["sink"] - want["sink"])) > 100 * limit


def test_config_kernel_ops_are_the_networks():
    prog = harness.build_program(dict(CONFIG, block=256))
    (dp,) = prog.device_programs().values()
    (fused,) = dp.actors
    program = prog.module.actors[fused].impl.stream_program
    kernel = CONFIG["kernel"]
    assert [op.kind for op in program.ops] == [k for k, *_ in kernel["ops"]]
    for op, (kind, *params) in zip(program.ops, kernel["ops"]):
        if kind != "delay":
            assert list(op.params) == pytest.approx(params)
    assert program.n_state == kernel["state_registers"] == 29
    assert (program.n_inputs, len(program.outputs)) == (
        kernel["in_wires"], kernel["out_wires"])
    assert dp.megastep_k == 4 and dp.flat_megastep


def test_stateful_work_counts_state_bytes_and_no_delay_ops():
    ops = CONFIG["kernel"]["ops"]
    assert stateful_fused.op_count("delay", ()) == 0
    n_ops, n_bytes = stateful_fused.call_work(ops, 1, 2, 1000, 3, 29)
    assert n_ops == 2 * 29 * 1000          # one axpy per tap
    assert n_bytes == 12 * 1000 + 3 * 29 * 8


def _ctx(calls, peaks, tel0=None, tel1=None):
    trace = SimpleNamespace(calls_of=lambda name: calls)
    return SimpleNamespace(trace=trace, config=CONFIG, peaks=peaks, log=print,
                           tel0=tel0, tel1=tel1)


def test_stateful_roofline_reader_on_a_synthetic_trace():
    shape = "f32[32,2,128,128]"   # 32 lanes x 2 wires x 16,384 tokens
    calls = [DeviceOp("vmap_stream_fused_.1", 0.0, 2e5,
                      f"%vmap_stream_fused_.1 = ({shape}{{3,2,1,0}}, "
                      f"f32[32,2,128]{{2,1,0}}) custom-call()")] * 3
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    tokens, lanes = 3 * 32 * 16384, 3 * 32
    want_bytes = 12 * tokens + lanes * 29 * 8
    got = stateful_fused_roofline.read(_ctx(calls, peaks))
    assert got == pytest.approx(100 * want_bytes / 819e9 / 6e-4)
    assert stateful_fused_roofline.read(_ctx(calls, None)) is None
    assert stateful_fused_roofline.read(_ctx([], peaks)) is None
    stateless = dict(CONFIG, kernel=dict(CONFIG["kernel"], state_registers=0))
    assert stateful_fused_roofline.read(
        SimpleNamespace(**dict(vars(_ctx(calls, peaks)), config=stateless))
    ) is None


def test_blocks_per_lane_reader():
    tel = lambda lanes, blocks: SimpleNamespace(  # noqa: E731
        device_lanes=lanes, device_blocks=blocks)
    assert blocks_per_lane.read(_ctx([], None, tel(10, 30), tel(74, 286))) \
        == 4.0
    assert blocks_per_lane.read(_ctx([], None, tel(10, 30), tel(10, 30))) \
        is None
    # a program without the counter reads nothing
    old = SimpleNamespace(device_lanes=10)
    assert blocks_per_lane.read(_ctx([], None, old, old)) is None
