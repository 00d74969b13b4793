"""Session ingress without per-token boxing: a source that feeds a device
partition gets an ``ArrayFifo`` admission queue and ingress FIFO, ``submit``
copies a submission once into a numpy block, the pump moves blocks, and
staging casts block slices into the launch's staging row; the
``tokens_pumped_blocks`` counter and its benchmark reader.

The reference for bit identity is the ``RingFifo`` path that every
host-consumed source keeps, forced here for a device-fed one by putting no
source on the array path."""

import itertools
import sys
import threading
import time
from types import SimpleNamespace

import ml_dtypes
import numpy as np
import pytest

import repro
from repro import checkpoint as ckpt
from repro.apps.streams import NETWORKS
from repro.core.actor import Action, Actor, Port
from repro.core.xcf import make_xcf
from repro.frontend import network
from repro.observability import snapshot_from_trace
from repro.runtime.fifo import ArrayFifo, ReaderEndpoint, RingFifo
from repro.serve_stream import AdmissionFull, StreamServer
from repro.serve_stream import engine as engine_mod
from repro.serve_stream import recovery as recovery_mod
from repro.serve_stream import session as session_mod
from repro.serve_stream.session import _stage_into, _token_block
from bench import harness
from bench.metrics import ingress_block_pct
from bench.tests.test_bench_cells import tiny
from helpers import drain_source

BLOCK = 256


def _ring_path(mp):
    """Serve every source through the ``RingFifo`` path."""
    for mod in (session_mod, engine_mod, recovery_mod):
        mp.setattr(mod, "device_fed_ingress", lambda module: set())


def _compiled(name, size, block=BLOCK):
    net, _ = NETWORKS[name](size)
    return repro.compile(net, backend="device", block=block)


def _reference(name, size):
    net, got = NETWORKS[name](size)
    prog = repro.compile(net, backend="device", block=BLOCK)
    stream = drain_source(prog.graph)
    prog.run()
    return stream, list(got)


def _serve(prog, feeds, ports=("sink",), **kw):
    """Serve one session per feed (a list of submissions, interleaved
    across sessions); each session's outputs per port as one ndarray."""
    with prog.serve(**kw) as server:
        ss = [server.open_session() for _ in feeds]
        for chunks in itertools.zip_longest(*feeds):
            for s, chunk in zip(ss, chunks):
                if chunk is not None:
                    s.submit(chunk)
        for s in ss:
            s.close()
        assert server.drain(timeout=120)
        life = server.telemetry.lifetime()
        payload = server.trace() if kw.get("trace") else None
    outs = [{p: np.asarray(s.output(port=p)) for p in ports} for s in ss]
    return outs, life, ss, payload


def _both_paths(make_prog, feeds, ports=("sink",)):
    array_out, life, ss, _ = _serve(make_prog(), feeds, ports)
    assert all(type(q) is ArrayFifo for s in ss for q in s.queues.values())
    assert life.tokens_pumped_blocks == life.tokens_pumped > 0
    with pytest.MonkeyPatch.context() as mp:
        _ring_path(mp)
        ring_out, ring_life, ring_ss, _ = _serve(make_prog(), feeds, ports)
    assert all(type(q) is RingFifo for s in ring_ss for q in s.queues.values())
    assert ring_life.tokens_pumped_blocks == 0
    return array_out, ring_out


def _assert_bitwise(got, want):
    for g, w in zip(got, want, strict=True):
        assert set(g) == set(w)
        for port in g:
            assert g[port].dtype == w[port].dtype
            assert g[port].tobytes() == w[port].tobytes(), port


def _chunked(tokens, size):
    return [tokens[i:i + size] for i in range(0, len(tokens), size)]


# ---------------------------------------------------------------------------
# Delivered tokens: bit-identical to the RingFifo path
# ---------------------------------------------------------------------------


def _inputs(kind, n, seed):
    """``n`` client tokens of one kind."""
    rng = np.random.default_rng(seed)
    if kind == "floats":
        return (rng.standard_normal(n) * 1000.0).tolist()
    if kind == "ints":
        big = [2 ** 24 + 1, -(2 ** 25) - 3, 2 ** 53 + 1, 2 ** 62 + 2 ** 38 + 1]
        ints = rng.integers(-(2 ** 40), 2 ** 40, n).tolist()
        ints[:len(big)] = big        # a C double's rounding shows above 2^53
        return ints
    if kind == "mixed":
        return [int(v) if i % 3 else float(v) + 0.25
                for i, v in enumerate(rng.integers(-(2 ** 30), 2 ** 30, n))]
    assert kind == "float64_array"
    return rng.standard_normal(n) * 1e6


@pytest.mark.parametrize("kind", ["floats", "ints", "mixed", "float64_array"])
def test_delivered_tokens_bitwise_equal_the_ring_path(kind):
    """ZigZag permutes its staged tokens, so each output is exactly what
    staging made of one client token."""
    n = 64 * 40
    feeds = [_chunked(_inputs(kind, n, seed), 320) for seed in (1, 2)]
    array_out, ring_out = _both_paths(lambda: _compiled("ZigZag", 8), feeds)
    assert array_out[0]["sink"].dtype == np.float32
    _assert_bitwise(array_out, ring_out)


FIR29 = harness.load_cell("fir29_lowpass.serve.audio64").config


def _fir29_program():
    net, _ = harness.builder(FIR29).build(FIR29)
    return repro.compile(net, backend="device", block=4096)


@pytest.mark.parametrize("network_name", ["idct8", "fir29_lowpass"])
def test_1024_token_chunks_under_a_4096_block(network_name):
    """Four client chunks fill one staging row: staging copies from several
    queued blocks into one row."""
    rng = np.random.default_rng(5)
    if network_name == "idct8":
        make, ports = (lambda: _compiled("IDCT8", 8, block=4096)), ("sink",)
        vals = rng.integers(-2048, 2048, (3, 20_480))
    else:
        make, ports = _fir29_program, ("sink", "xsink")
        vals = rng.integers(-32768, 32768, (3, 20_480))
    feeds = [_chunked(v.astype(float).tolist(), 1024) for v in vals]
    array_out, ring_out = _both_paths(make, feeds, ports)
    assert len(array_out[0]["sink"]) == vals.shape[1]
    _assert_bitwise(array_out, ring_out)


def test_client_mutating_its_buffer_after_submit_changes_nothing():
    stream, ref = _reference("IDCT8", 64)
    arr = np.asarray(stream, np.float64)
    lst = list(stream)
    with _compiled("IDCT8", 64).serve() as server:
        a, b = server.open_session(), server.open_session()
        a.submit(arr)
        b.submit(lst)
        arr[:] = 0.0
        lst[:] = [0.0] * len(lst)
        for s in (a, b):
            s.close()
        assert server.drain(timeout=120)
    assert a.output() == ref and b.output() == ref


# ---------------------------------------------------------------------------
# Admission semantics, on either kind of queue
# ---------------------------------------------------------------------------

HOST_DESCALE = make_xcf("IDCT8", {"source": "t0", "descale": "t0",
                                  "idct": "accel", "clip": "accel",
                                  "sink": "t0"})


def _unstarted(placement, **kw):
    prog = _compiled("IDCT8", 64)
    if placement == "host":
        prog = prog.repartition(HOST_DESCALE)
    server = prog.serve(admission_depth=128, **kw)
    s = server.open_session()
    q = s.queues["source"]
    assert type(q) is (ArrayFifo if placement == "device" else RingFifo)
    return server, s, q


def _queued(q):
    q.snapshot_reader()
    return q.count()


@pytest.mark.parametrize("placement", ["device", "host"])
def test_admission_full_and_timeout_keep_their_semantics(placement):
    server, s, q = _unstarted(placement)
    s.submit([1.0] * 100)
    with pytest.raises(AdmissionFull, match="full"):
        s.submit([2.0] * 29, block=False)        # all of it, or nothing
    assert _queued(q) == 100
    s.submit([2.0] * 28, block=False)            # fits exactly
    server.wait_for_space = lambda deadline: False   # the deadline passes
    with pytest.raises(AdmissionFull, match="timed out"):
        s.submit([3.0] * 8, timeout=0.01)
    assert _queued(q) == 128 and s.submitted_tokens == 128
    assert list(q.peek(128))[99:101] == [1.0, 2.0]
    assert s.first_submit_ns is not None


@pytest.mark.parametrize("placement", ["device", "host"])
def test_split_submission_keeps_its_semantics(placement):
    stream, ref = _reference("IDCT8", 64)
    prog = _compiled("IDCT8", 64)
    if placement == "host":
        prog = prog.repartition(HOST_DESCALE)
    with prog.serve(admission_depth=256, admission_chunk=96) as server:
        s = server.open_session()
        s.submit(stream)
        s.close()
        assert server.drain(timeout=120)
        life = server.telemetry.lifetime()
    assert s.output() == ref
    assert life.chunks_split == 1
    assert life.chunks_submitted == -(-len(stream) // 96)
    assert life.tokens_submitted == life.tokens_pumped == len(stream)


# ---------------------------------------------------------------------------
# A host-consumed ingress keeps RingFifo and Python objects
# ---------------------------------------------------------------------------


def _passthrough_network():
    net = network("PassTuples")
    src = net.source("source", lambda st: (st, None), has_next=lambda st: False)
    fwd = net.add(Actor(
        "fwd", inputs=[Port("IN", "float32")], outputs=[Port("OUT", "float32")],
        actions=[Action("f", consumes={"IN": 1}, produces={"OUT": 1},
                        fire=lambda st, t: (st, {"OUT": list(t["IN"])}))]))
    net.connect(src.OUT, fwd.IN)
    net.connect(fwd.OUT, net.sink("sink", collect=[]).IN)
    return net


def test_host_consumed_ingress_keeps_ring_fifos_and_python_objects():
    tokens = [(i, "tuple") for i in range(50)] + [1.5, 7, "text", None]
    prog = repro.compile(_passthrough_network(), backend="host")
    with prog.serve() as server:
        s = server.open_session()
        assert type(s.queues["source"]) is RingFifo
        assert type(s.pipeline.ingress["source"]) is RingFifo
        s.submit(tokens[:30])
        s.submit(tuple(tokens[30:]))
        s.close()
        assert server.drain(timeout=60)
        life = server.telemetry.lifetime()
    out = list(s.output())
    assert out == tokens
    assert all(a is b for a, b in zip(out, tokens))
    assert life.tokens_pumped == len(tokens) and life.tokens_pumped_blocks == 0


@pytest.mark.parametrize("placement, kind", [
    ("device", ArrayFifo), ("host_descale", RingFifo), ("host", RingFifo),
])
def test_ingress_fifos_follow_the_consumer_placement(placement, kind):
    prog = _compiled("IDCT8", 8)
    if placement == "host_descale":
        prog = prog.repartition(HOST_DESCALE)
    elif placement == "host":
        prog = prog.repartition(backend="host")
    server = prog.serve()
    try:
        s = server.open_session()
        assert type(s.queues["source"]) is kind
        assert type(s.pipeline.ingress["source"]) is kind
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Residue: checkpoint and restore, forced degrade to host
# ---------------------------------------------------------------------------


def test_checkpoint_restore_with_array_residue(tmp_path):
    stream, ref = _reference("IDCT8", 1024)
    server = _compiled("IDCT8", 1024).serve(admission_depth=4096)
    s = server.open_session()
    pumped = 0
    for lo, hi in ((0, 3000), (3000, 5000)):
        s.submit(stream[lo:hi])
        moved, blocks = s.pipeline.pump(server.telemetry)
        assert moved == blocks
        pumped += moved
    assert pumped < 5000                      # the ingress FIFO is full
    s.submit(np.asarray(stream[5000:5800]))   # a second queued block
    server.checkpoint(tmp_path)
    flat, _extra = ckpt.load_flat(tmp_path, ckpt.latest_step(tmp_path))
    queued = flat[f"s{s.sid}/queue/source"]   # two blocks, one array
    assert queued.dtype == np.float64
    np.testing.assert_array_equal(queued, stream[pumped:5800])
    (fifo_residue,) = [v for k, v in flat.items() if "/fifo/" in k]
    assert len(fifo_residue) == pumped

    server2 = StreamServer.recover(_compiled("IDCT8", 1024), tmp_path,
                                   start=True)
    try:
        s2 = server2.session(s.sid)
        assert type(s2.queues["source"]) is ArrayFifo
        s2.submit(stream[5800:])
        s2.close()
        assert server2.drain(timeout=120)
        assert s2.output() == ref
    finally:
        server2.stop()


def test_forced_degrade_converts_array_residue_for_the_host():
    stream, ref = _reference("TopFilter", 20_000)
    prog = _compiled("TopFilter", 20_000)
    with prog.serve(chaos="launch:*|after=2", launch_retries=1,
                    retry_base_s=0.001) as server:
        s = server.open_session()
        for chunk in _chunked(stream, 500):
            s.submit(chunk)
        s.close()
        assert server.drain(timeout=120)
        assert server._g_degraded.value == 1
        life = server.telemetry.lifetime()
    assert type(s.queues["source"]) is ArrayFifo
    assert type(s.pipeline.ingress["source"]) is RingFifo
    assert 0 < life.tokens_pumped_blocks < life.tokens_pumped
    assert life.tokens_pumped == len(stream)
    assert s.output() == ref


def test_mixed_dtype_queue_residue_checkpoints_as_python_scalars():
    """Queued blocks of different dtypes (a float submission, then an int
    one) checkpoint as the Python scalars a host actor would consume."""
    q = ArrayFifo(64, name="q")
    for chunk in ([0.5, 1.5], [2 ** 60 + 1, 3]):
        q.write(_token_block(chunk))
    arr = recovery_mod._tokens_arr(q.peek_blocks(q.count()))
    assert arr.dtype == object
    assert arr.tolist() == [0.5, 1.5, 2 ** 60 + 1, 3]
    assert [type(v) for v in arr] == [float, float, int, int]
    ring = RingFifo(64, name="r", deferred=False)
    session_mod._write_tokens(ring, arr.tolist())
    assert [type(v) for v in ring.read(4)] == [float, float, int, int]


def test_carry_fifos_hands_a_ring_ingress_python_scalars():
    prog = _compiled("IDCT8", 8)
    server = prog.serve()
    s = server.open_session()
    s.submit([0.5] * 64)
    s.pipeline.pump()
    residue = s.pipeline.carry_fifos()
    (toks,) = residue.values()
    assert isinstance(toks, np.ndarray) and toks.dtype == np.float64
    pipe = session_mod.SessionPipeline(
        prog.repartition(backend="host").module, s, None,
        carry_fifos=residue,
    )
    ring = pipe.ingress["source"]
    assert type(ring) is RingFifo
    got = ring.read(ring.count())
    assert got == (0.5,) * 64 and all(type(v) is float for v in got)


# ---------------------------------------------------------------------------
# The counter and its benchmark reader
# ---------------------------------------------------------------------------


def test_device_ingress_counts_every_pumped_token_as_blocks():
    stream, ref = _reference("IDCT8", 64)
    outs, life, ss, payload = _serve(
        _compiled("IDCT8", 64), [_chunked(stream, 96)] * 3, trace=True)
    assert life.tokens_pumped == life.tokens_submitted == 3 * len(stream)
    assert life.tokens_pumped_blocks == life.tokens_pumped
    assert snapshot_from_trace(payload).tokens_pumped_blocks == (
        life.tokens_pumped_blocks
    )
    assert all(s.output() == ref for s in ss)


def test_host_ingress_counts_no_blocks():
    prog = _compiled("IDCT8", 32).repartition(HOST_DESCALE)
    stream = drain_source(prog.graph)
    _outs, life, _ss, _ = _serve(prog, [_chunked(stream, 96)] * 2)
    assert life.tokens_pumped == 2 * len(stream)
    assert life.tokens_pumped_blocks == 0


def test_ingress_block_pct_reader():
    def tel(pumped, blocks=None):
        t = SimpleNamespace(tokens_pumped=pumped)
        if blocks is not None:
            t.tokens_pumped_blocks = blocks
        return t

    ctx = SimpleNamespace(tel0=tel(100, 40), tel1=tel(300, 190))
    assert ingress_block_pct.read(ctx) == pytest.approx(75.0)
    # a program without the counter, or a window that pumped nothing
    assert ingress_block_pct.read(
        SimpleNamespace(tel0=tel(100), tel1=tel(300))) is None
    assert ingress_block_pct.read(
        SimpleNamespace(tel0=tel(100, 0), tel1=tel(100, 0))) is None


def test_ingress_block_pct_reads_100_on_a_recorded_serve(tmp_path,
                                                          monkeypatch):
    """The served IDCT8 cell at test size, traced through the benchmark's
    harness: every pumped token moved as a block."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    result = harness.run(tiny("idct8.serve.streams64"), 2 ** 31 + 13, 1.0,
                         True, time.perf_counter(), platform=None,
                         trace_dir=tmp_path)
    assert result["correct"] is True
    assert result["metrics"]["ingress_block_pct.tput"]["value"] == 100.0


# ---------------------------------------------------------------------------
# The pieces: conversion at submit, the cast at staging, the FIFO calls
# ---------------------------------------------------------------------------


def _tokens(name):
    return {
        "floats": [0.1, -2.5, 1e300, 3.0],
        "floats_then_ints": [0.5, 1, 2 ** 60 + 12345, True],
        "float_then_text": [0.5, "x"],
        "ints": [1, 2 ** 62, -3],
        "tuple": (1.5, 2.5),
        "generator": (float(i) for i in range(4)),
        "float32_array": np.arange(4, dtype=np.float32),
        "numpy_scalars": [np.float32(1.25), np.float32(2.5)],
    }[name]


@pytest.mark.parametrize("name", [
    "floats", "floats_then_ints", "float_then_text", "ints", "tuple",
    "generator", "float32_array", "numpy_scalars",
])
def test_token_block_is_a_copy_with_the_dtype_numpy_infers(name):
    src = _tokens(name)
    want = np.array(list(_tokens(name)))
    got = _token_block(src)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    if isinstance(src, (list, np.ndarray)):
        src[0] = src[1]                          # the client reuses it
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16, np.int32])
@pytest.mark.parametrize("kind", ["floats", "ints", "mixed"])
def test_stage_cast_equals_the_list_conversion(dtype, kind):
    """Each block kind staged into a row of each port dtype equals what
    ``np.asarray(python_tokens, dtype)`` made of the same tokens."""
    rng = np.random.default_rng(9)
    if kind == "floats":
        toks = (rng.standard_normal(512) * 3e4).tolist()
    elif kind == "ints":
        toks = rng.integers(-(2 ** 31), 2 ** 31, 512).tolist()
        if dtype is not np.int32:
            toks[:3] = [2 ** 53 + 1, 2 ** 62 + 2 ** 38 + 1,
                        2 ** 62 + 2 ** 54 + 1]
    else:
        toks = [int(v) if i % 2 else float(v) + 0.5
                for i, v in enumerate(rng.integers(-(2 ** 20), 2 ** 20, 512))]
    want = np.asarray(toks, dtype=dtype)
    for part in (_token_block(toks), toks):
        row = np.zeros(600, dtype)
        _stage_into(row[:512], part)
        assert row[:512].tobytes() == want.tobytes()
        assert not row[512:].any()


def test_ring_fifo_read_blocks_are_its_contiguous_slices():
    f = RingFifo(8, deferred=False)
    f.write(list(range(6)))
    f.read(5)
    f.write([6, 7, 8, 9, 10])
    assert ReaderEndpoint(f).read_blocks(5) == [[5, 6, 7], [8, 9]]
    assert f.count() == 1 and f.read_blocks(1) == [[10]]


def test_array_fifo_peek_blocks_leaves_the_tokens():
    f = ArrayFifo(16)
    f.write(np.arange(3.0))
    f.write(np.arange(3, 7, dtype=np.int64))
    f.read_blocks(1)
    blocks = f.peek_blocks(5)
    assert [b.tolist() for b in blocks] == [[1.0, 2.0], [3, 4, 5]]
    assert [b.dtype for b in blocks] == [np.float64, np.int64]
    assert f.count() == 6
    assert f.peek(5).tolist() == [1.0, 2.0, 3.0, 4.0, 5.0]
    assert [b.tolist() for b in ReaderEndpoint(f).read_blocks(5)] == [
        [1.0, 2.0], [3, 4, 5]]
    assert f.count() == 1 and f.peek_blocks(1)[0].tolist() == [6]


def test_array_queue_one_writer_one_reader_under_thread_switches():
    """The admission queue's contract: a client thread writes blocks, the
    engine thread reads them; no token is lost, repeated or reordered."""
    q = ArrayFifo(64)
    total = 20_000
    got = []

    def writer():
        i = 0
        while i < total:
            n = min(7, total - i)
            while q.space() < n:
                time.sleep(0)
            q.write(np.arange(i, i + n))
            i += n

    def reader():
        seen = 0
        while seen < total:
            n = q.count()
            if not n:
                time.sleep(0)
                continue
            got.extend(b.copy() for b in q.read_blocks(n))
            seen += n

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=f) for f in (writer, reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    np.testing.assert_array_equal(np.concatenate(got), np.arange(total))


def test_many_client_threads_submit_concurrently():
    stream, ref = _reference("IDCT8", 64)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _compiled("IDCT8", 64).serve(admission_depth=96) as server:
            ss = [server.open_session() for _ in range(12)]

            def client(s):
                for chunk in _chunked(stream, 40):
                    s.submit(chunk)
                s.close()

            threads = [threading.Thread(target=client, args=(s,))
                       for s in ss]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert server.drain(timeout=120)
            life = server.telemetry.lifetime()
    finally:
        sys.setswitchinterval(old)
    assert all(s.output() == ref for s in ss)
    assert life.tokens_pumped_blocks == life.tokens_pumped == 12 * len(stream)
