"""Stateful fused device regions: the delay-line FIR tap (``FirTap``) fused
into one stream program whose ``delay`` ops carry the taps' registers.

Bitwise here means on the CPU (the jnp reference path, and the Pallas
kernel in interpret mode); on the chip a fused FIR chain rounds differently
from the unfused one (ROADMAP D7).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.apps.streams import FirSeed, FirTap, masked_delay
from repro.frontend import network
from repro.frontend.program import synthesize_xcf
from repro.kernels.stream_fused import fused_stream
from repro.serve_stream import StreamServer
from repro.serve_stream.session import (
    DeviceStage,
    _flatten_device_state,
    _transplant_device_state,
)

BLOCK = 256
# dyadic taps: on 16-bit integer samples every product and sum is exact in
# float32, so host (float64) and device (float32) runs agree bitwise, and a
# run that moves between them mid-stream must equal both
TAPS = (0.125, -0.25, 0.5, 0.75, -0.0625)
CHUNKS = (1, 7, 1023, 4095, 4097, 16385)


def build(values=(), taps=TAPS):
    net = network("FIRD")
    vals = [float(v) for v in values]
    src = net.source(
        "source",
        lambda st: ({**st, "i": st.get("i", 0) + 1}, vals[st.get("i", 0)]),
        has_next=lambda st: st.get("i", 0) < len(vals),
    )
    seed = net.add(FirSeed, "seed")
    src.OUT >> seed.IN
    prev = seed
    for i, c in enumerate(taps):
        tap = net.add(FirTap(float(c)), f"tap{i}")
        prev.XOUT >> tap.XIN
        prev.AOUT >> tap.AIN
        prev = tap
    got = {"sink": [], "xsink": []}
    prev.AOUT >> net.sink("sink", collect=got["sink"]).IN
    prev.XOUT >> net.sink("xsink", collect=got["xsink"]).IN
    return net, got


def samples(n, seed=0):
    return np.random.default_rng(seed).integers(-32768, 32768, n).astype(
        float)


def run(x, **compile_kw):
    net, got = build(x)
    prog = repro.compile(net, **compile_kw)
    prog.run()
    return {k: np.asarray(v, np.float64) for k, v in got.items()}, prog


def device_ref(x):
    return run(x, backend="device", block=BLOCK)[0]


def fused_program(megastep="auto", fuse=True):
    net, _ = build()
    prog = repro.compile(net, backend="device", block=BLOCK,
                         megastep=megastep, fuse=fuse)
    (dp,) = prog.device_programs().values()
    return prog, dp


# ---------------------------------------------------------------------------
# the tap, the program and the kernel
# ---------------------------------------------------------------------------


def test_tap_host_run_is_the_convolution():
    x = samples(3000)
    got, _ = run(x, backend="host")
    want = np.convolve(x, TAPS)[:len(x)]
    np.testing.assert_allclose(got["sink"], want, rtol=1e-6, atol=1e-9)
    n = len(TAPS)
    np.testing.assert_array_equal(got["xsink"], np.r_[np.zeros(n), x[:-n]])
    # the premise of the fault tests below
    dev = device_ref(x)
    for port in ("sink", "xsink"):
        np.testing.assert_array_equal(dev[port], got[port])


@pytest.mark.parametrize("mask", ["prefix", "holes", "none"])
def test_masked_delay_skips_padding(mask):
    x = jnp.arange(1.0, 11.0)
    m = {"prefix": np.arange(10) < 6,
         "holes": np.array([1, 0, 0, 1, 1, 0, 1, 0, 0, 0], bool),
         "none": np.zeros(10, bool)}[mask]
    y, z = masked_delay(x, jnp.asarray(m), -5.0)
    valid = np.flatnonzero(m)
    want = np.r_[-5.0, np.asarray(x)[valid][:-1]]
    np.testing.assert_array_equal(np.asarray(y)[valid], want[:len(valid)])
    assert float(z) == (float(x[valid[-1]]) if len(valid) else -5.0)


def test_region_fuses_into_one_stateful_program():
    prog, dp = fused_program()
    (fused,) = dp.actors
    program = prog.module.actors[fused].impl.stream_program
    kinds = [op.kind for op in program.ops]
    assert kinds == ["const"] + ["delay", "axpy"] * len(TAPS)
    assert program.n_state == len(TAPS)
    assert dp.flat_megastep
    # one register vector per lane; the slots name each register's tap
    assert dp.init_state[fused]["regs"].shape == (len(TAPS),)
    assert dp.state_slots[fused] == tuple(
        (f"tap{i}", "z") for i in range(len(TAPS)))


def test_fused_equals_unfused_bitwise():
    x = samples(5000, 1)
    fused, fp = run(x, backend="device", block=BLOCK)
    unfused, up = run(x, backend="device", block=BLOCK, fuse=False)
    for port in ("sink", "xsink"):
        np.testing.assert_array_equal(fused[port], unfused[port])
    assert len(up.device_program().actors) > len(fp.device_program().actors)


@pytest.mark.parametrize("lead", [(), (4,)])
def test_pallas_interpret_equals_reference_bitwise(lead):
    prog, dp = fused_program()
    (fused,) = dp.actors
    program = prog.module.actors[fused].impl.stream_program
    rng = np.random.default_rng(3)
    lanes = 3
    x = jnp.asarray(rng.integers(-32768, 32768, (lanes, *lead, BLOCK))
                    .astype(np.float32))
    state = jnp.asarray(rng.integers(-9, 9, (lanes, program.n_state))
                        .astype(np.float32))
    size = int(np.prod(lead or (1,))) * BLOCK
    counts = jnp.asarray([[size], [size // 2 + 3], [0]], jnp.int32)

    def lane(use):
        return jax.vmap(lambda v, s, c: fused_stream(
            [v], program, use=use, state=s, counts=c))(x, state, counts)

    (ref, ref_state), (ker, ker_state) = lane("ref"), lane("pallas")
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ref_state),
                                  np.asarray(ker_state))
    # the empty lane keeps its registers
    np.testing.assert_array_equal(np.asarray(ker_state[2]),
                                  np.asarray(state[2]))


def test_flat_k4_launch_equals_four_k1_launches_bitwise():
    _, dp4 = fused_program(megastep=4)
    _, dp1 = fused_program(megastep=False)
    assert dp4.megastep_k == 4 and dp4.flat_megastep and dp1.megastep_k == 1
    (key,) = [f"{a}.{p}" for a, p, _ in dp4.in_ports]
    rng = np.random.default_rng(5)
    vals = rng.integers(-32768, 32768, (4, BLOCK)).astype(np.float32)
    mask = (np.arange(4 * BLOCK) < 3 * BLOCK + 17).reshape(4, BLOCK)
    vals[~mask] = 0.0
    state4, outs4, _ = dp4.megastep(
        dp4.fresh_state(), {key: (jnp.asarray(vals), jnp.asarray(mask))})
    state1 = dp1.fresh_state()
    outs1 = []
    for j in range(4):
        state1, outs, _ = dp1.step(
            state1, {key: (jnp.asarray(vals[j]), jnp.asarray(mask[j]))})
        outs1.append(outs)
    for port, (v4, m4) in outs4.items():
        np.testing.assert_array_equal(
            np.asarray(v4), np.stack([np.asarray(o[port][0]) for o in outs1]))
        np.testing.assert_array_equal(
            np.asarray(m4), np.stack([np.asarray(o[port][1]) for o in outs1]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state4, state1)


@pytest.mark.parametrize("fuse,k", [(True, 4), (False, 1)])
def test_megastep_k_for_stateful_regions(fuse, k):
    _, dp = fused_program(megastep=4, fuse=fuse)
    assert dp.megastep_k == k
    assert bool(dp.state_slots) is fuse


# ---------------------------------------------------------------------------
# serve(): ragged chunks, the block counter, state across faults
# ---------------------------------------------------------------------------


def _submit_ragged(session, x):
    i = j = 0
    while i < len(x):
        n = CHUNKS[j % len(CHUNKS)]
        session.submit(x[i:i + n].tolist())
        i, j = i + n, j + 1


@pytest.mark.parametrize("sessions", [1, 3])
def test_serve_ragged_chunks_equal_one_run_bitwise(sessions):
    x = samples(30_000, 2)
    ref = device_ref(x)
    net, _ = build()
    prog = repro.compile(net, backend="device", block=BLOCK)
    with prog.serve(max_batch=4) as server:
        ss = [server.open_session() for _ in range(sessions)]
        for s in ss:
            _submit_ragged(s, x)
            s.close()
        assert server.drain(timeout=120)
        for s in ss:
            for port in ("sink", "xsink"):
                np.testing.assert_array_equal(
                    np.asarray(s.output(port=port), np.float64), ref[port])


def test_device_blocks_counts_k_per_lane():
    x = samples(12 * BLOCK, 4)
    net, _ = build()
    prog = repro.compile(net, backend="device", block=BLOCK)
    k = prog.device_program().megastep_k
    assert k == 4
    with prog.serve(trace=True, max_batch=4) as server:
        ss = [server.open_session() for _ in range(2)]
        for s in ss:
            s.submit(x.tolist())
            s.close()
        assert server.drain(timeout=120)
        life = server.telemetry.lifetime()
        events = server.recorder.events()
    launches = [e[6] for e in events
                if e[0] == "X" and e[3] == "device" and e[2] == "launch"]
    assert sum(a["blocks"] for a in launches) == life.device_blocks
    assert sum(a["lanes"] for a in launches) == life.device_lanes
    # every lane staged whole blocks of a prefix: blocks = tokens / block,
    # at most k per lane, and k where the stream was there to fill them
    assert life.device_blocks * BLOCK == life.device_tokens_in
    assert all(a["blocks"] <= k * a["lanes"] for a in launches)
    assert any(a["blocks"] == k * a["lanes"] for a in launches)
    assert life.device_blocks / life.device_lanes > 1


def test_hot_swap_carries_the_delay_lines():
    x = samples(40_000, 6)
    ref = device_ref(x)
    net, _ = build()
    prog = repro.compile(net, backend="device", block=BLOCK)
    with prog.serve() as server:
        ss = [server.open_session() for _ in range(2)]
        for s in ss:
            s.submit(x[:15_000].tolist())
        time.sleep(0.2)
        server.request_repartition(synthesize_xcf(prog.graph, "host"))
        deadline = time.time() + 60
        while server.telemetry.lifetime().swaps < 1 and time.time() < deadline:
            time.sleep(0.01)
        for s in ss:
            s.submit(x[15_000:25_000].tolist())
        server.request_repartition(synthesize_xcf(prog.graph, "device"))
        for s in ss:
            s.submit(x[25_000:].tolist())
            s.close()
        assert server.drain(timeout=120)
        assert server.telemetry.lifetime().swaps == 2
        for s in ss:
            for port in ("sink", "xsink"):
                np.testing.assert_array_equal(
                    np.asarray(s.output(port=port), np.float64), ref[port])


def test_degrade_to_host_mid_stream_carries_the_delay_lines():
    x = samples(40_000, 7)
    ref = device_ref(x)
    net, _ = build()
    prog = repro.compile(net, backend="device", block=BLOCK)
    with prog.serve(chaos="launch:*|after=6", launch_retries=1,
                    retry_base_s=0.001) as server:
        s = server.open_session()
        _submit_ragged(s, x)
        s.close()
        assert server.drain(timeout=120)
        assert server._quarantined and server.program.hw_partition is None
        life = server.telemetry.lifetime()
        assert life.swaps == 1 and 0 < life.device_tokens_out < len(x)
        for port in ("sink", "xsink"):
            np.testing.assert_array_equal(
                np.asarray(s.output(port=port), np.float64), ref[port])


def test_checkpoint_restore_carries_the_delay_lines(tmp_path):
    x = samples(20_000, 8)
    ref = device_ref(x)
    half = 9_000
    net, _ = build()
    server = repro.compile(net, backend="device", block=BLOCK).serve(
        start=True)
    s = server.open_session()
    s.submit(x[:half].tolist())
    deadline = time.time() + 60
    while s.first_delivery_ns is None and time.time() < deadline:
        time.sleep(0.005)
    server.checkpoint(tmp_path)
    server.kill()
    net2, _ = build()
    server2 = StreamServer.recover(
        repro.compile(net2, backend="device", block=BLOCK), tmp_path,
        start=True)
    try:
        s2 = server2.session(0)
        s2.submit(x[half:].tolist())
        s2.close()
        assert server2.drain(timeout=120)
        for port in ("sink", "xsink"):
            np.testing.assert_array_equal(
                np.asarray(s2.output(port=port), np.float64), ref[port])
    finally:
        server2.stop()


def test_flatten_and_transplant_map_registers_to_taps():
    fprog, fdp = fused_program()
    _, udp = fused_program(fuse=False)
    stage = DeviceStage(fdp, fprog.module)
    (fused,) = fdp.actors
    stage.state = {fused: {"regs": jnp.arange(len(TAPS), dtype=jnp.float32)
                           + 0.5}}
    flat = _flatten_device_state(stage)
    assert flat[f"tap{len(TAPS) - 1}"] == {"z": len(TAPS) - 0.5}
    assert flat["seed"] == {}
    assert all(isinstance(v["z"], float) for m, v in flat.items()
               if m.startswith("tap"))
    # onto the unfused placement, one actor per tap, and back
    per_actor = _transplant_device_state(udp, udp.init_state, flat)
    assert float(per_actor["tap1"]["z"]) == 1.5
    assert per_actor["tap1"]["z"].dtype == jnp.float32
    back = _transplant_device_state(fdp, fdp.init_state, flat)
    np.testing.assert_array_equal(np.asarray(back[fused]["regs"]),
                                  np.arange(len(TAPS)) + 0.5)
    assert back[fused]["regs"].dtype == jnp.float32
