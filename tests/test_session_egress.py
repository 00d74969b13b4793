"""Session egress without per-token boxing: a device-fed sink channel is an
``ArrayFifo``, a session's result buffer (``DeliveredTokens``) keeps the
numpy blocks it is handed, the ``tokens_delivered_blocks`` counter and its
benchmark reader, and a checkpoint that stores a device-fed port's tokens as
one numeric array."""

import time
import tracemalloc
from types import SimpleNamespace

import numpy as np
import pytest

import repro
from repro import checkpoint as ckpt
from repro.apps.streams import NETWORKS
from repro.core.xcf import make_xcf
from repro.frontend.program import synthesize_xcf
from repro.observability import snapshot_from_trace
from repro.runtime.fifo import ArrayFifo, RingFifo
from repro.serve_stream import StreamServer
from repro.serve_stream.session import DeliveredTokens
from bench import harness
from bench.metrics import egress_block_pct
from bench.tests.test_bench_cells import tiny
from helpers import drain_source

BLOCK = 256


def _build(name, size):
    return NETWORKS[name](n=size) if name == "FIR32" else NETWORKS[name](size)


def _reference(name, size):
    net, got = _build(name, size)
    prog = repro.compile(net, backend="device", block=BLOCK)
    stream = drain_source(prog.graph)
    prog.run()
    return stream, list(got)


def _compiled(name, size):
    net, _ = _build(name, size)
    return repro.compile(net, backend="device", block=BLOCK)


# ---------------------------------------------------------------------------
# The buffer
# ---------------------------------------------------------------------------


def _sequence_behaviour():
    blocks = [np.arange(5, dtype=np.float32), np.arange(5, 9, dtype=np.float32)]
    buf = DeliveredTokens()
    for b in blocks:
        buf.extend(b)
    want = [float(v) for v in range(9)]
    assert len(buf) == 9
    assert buf[0] == 0.0 and buf[6] == 6.0 and buf[-1] == 8.0
    with pytest.raises(IndexError):
        buf[9]
    part = buf[3:7]
    assert isinstance(part, np.ndarray) and part.dtype == np.float32
    assert part.tolist() == [3.0, 4.0, 5.0, 6.0]
    assert buf[:0].dtype == np.float32 and buf[7:100].tolist() == [7.0, 8.0]
    assert buf[::3].tolist() == [0.0, 3.0, 6.0]
    assert list(buf) == want
    assert buf == want and want == buf
    assert not buf == want[:-1]                    # length differs
    assert buf != want[:-1] + [8.5]                # one value differs
    assert buf != "not a token list"
    assert all(isinstance(c, np.ndarray) for c in buf.chunks)


def _asarray_without_boxing():
    n = 1 << 20
    buf = DeliveredTokens()
    for b in np.split(np.arange(n, dtype=np.float32), 16):
        buf.extend(b)
    tracemalloc.start()
    try:
        arr = np.asarray(buf)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert arr.dtype == np.float32 and arr.shape == (n,)
    assert np.array_equal(arr, np.arange(n, dtype=np.float32))
    # one float32 copy of the tokens; a Python scalar per token would be
    # ~28 bytes each, seven times this
    assert peak < 2 * arr.nbytes
    assert np.asarray(buf, np.float64).dtype == np.float64


def _extend_arrays():
    buf = DeliveredTokens()
    buf.extend(np.array([1, 2], np.int32))
    buf.extend(np.array([], np.int32))             # nothing is stored
    buf.extend(np.array([3], np.int32))
    assert len(buf.chunks) == 2
    assert buf == [1, 2, 3] and buf[:].dtype == np.int32


def _extend_lists():
    buf = DeliveredTokens()
    buf.extend([0.5, 1.5])
    buf.extend((2.5,))                             # one list chunk grows
    buf.extend([])
    assert len(buf.chunks) == 1 and buf == [0.5, 1.5, 2.5]
    assert buf[1] == 1.5 and type(buf[1]) is float
    assert buf[0:2].tolist() == [0.5, 1.5]


def _extend_mixed():
    buf = DeliveredTokens()
    buf.extend(np.array([0.25, 0.5], np.float32))
    buf.extend([0.75, 1.0])
    buf.extend(np.array([1.25], np.float32))
    assert [type(c) for c in buf.chunks] == [np.ndarray, list, np.ndarray]
    assert len(buf) == 5 and buf[2] == 0.75 and buf[4] == 1.25
    assert buf == [0.25, 0.5, 0.75, 1.0, 1.25]
    assert buf[1:4].tolist() == [0.5, 0.75, 1.0]
    assert buf != [0.25, 0.5, 0.75, 1.0, 1.5]


def _carried_across_hot_swap():
    """Device-delivered blocks, then host-delivered lists, one buffer."""
    stream, ref = _reference("TopFilter", 2000)
    prog = _compiled("TopFilter", 2000)
    with prog.serve() as server:
        s = server.open_session()
        s.submit(stream[:1000])
        deadline = time.time() + 60
        while not len(s.output()) and time.time() < deadline:
            time.sleep(0.01)
        server.request_repartition(synthesize_xcf(prog.graph, "host"))
        s.submit(stream[1000:])
        s.close()
        assert server.drain(timeout=120)
        out = s.output()
        assert server.telemetry.lifetime().swaps == 1
    assert out == ref
    kinds = [type(c) for c in out.chunks]
    assert kinds[0] is np.ndarray and kinds[-1] is list


def _carried_across_degrade():
    stream, ref = _reference("TopFilter", 2000)
    prog = _compiled("TopFilter", 2000)
    with prog.serve(chaos="launch:*|after=2", launch_retries=1,
                    retry_base_s=0.001) as server:
        s = server.open_session()
        s.submit(stream[:1000])
        deadline = time.time() + 60
        while not len(s.output()) and time.time() < deadline:
            time.sleep(0.01)
        s.submit(stream[1000:])
        s.close()
        assert server.drain(timeout=120)
        assert server._g_degraded.value == 1
        out = s.output()
    assert out == ref
    assert any(isinstance(c, np.ndarray) for c in out.chunks)
    assert isinstance(out.chunks[-1], list)


BUFFER_CASES = {
    "sequence": _sequence_behaviour,
    "asarray_without_boxing": _asarray_without_boxing,
    "extend_arrays": _extend_arrays,
    "extend_lists": _extend_lists,
    "extend_mixed": _extend_mixed,
    "hot_swap": _carried_across_hot_swap,
    "degrade_to_host": _carried_across_degrade,
}


@pytest.mark.parametrize("case", sorted(BUFFER_CASES))
def test_delivered_tokens(case):
    BUFFER_CASES[case]()


# ---------------------------------------------------------------------------
# Wiring and the counter
# ---------------------------------------------------------------------------

HOST_CLIP = make_xcf("IDCT8", {"source": "t0", "descale": "accel",
                               "idct": "accel", "clip": "t0", "sink": "t0"})


def _placed(prog, placement):
    """IDCT8 all on the device, with the clip feeding the sink on the host,
    or all on the host."""
    if placement == "device":
        return prog
    return prog.repartition(synthesize_xcf(prog.graph, "host")
                            if placement == "host" else HOST_CLIP)


@pytest.mark.parametrize("placement, fifo", [
    ("device", ArrayFifo), ("host_clip", RingFifo), ("host", RingFifo),
])
def test_sink_fifo_follows_the_source_placement(placement, fifo):
    server = _placed(_compiled("IDCT8", 8), placement).serve()
    try:
        s = server.open_session()
        ((sink, f),) = s.pipeline.egress
        assert sink == "sink" and type(f) is fifo
    finally:
        server.stop()


def _serve(prog, stream, sessions=3, **kw):
    with prog.serve(**kw) as server:
        ss = [server.open_session() for _ in range(sessions)]
        for i in range(0, len(stream), 96):
            for s in ss:
                s.submit(stream[i:i + 96])
        for s in ss:
            s.close()
        assert server.drain(timeout=120)
        life = server.telemetry.lifetime()
        payload = server.trace() if kw.get("trace") else None
    return ss, life, payload


def test_device_sink_delivers_blocks_and_counts_them():
    stream, ref = _reference("IDCT8", 64)
    ss, life, payload = _serve(_compiled("IDCT8", 64), stream, trace=True)
    assert life.tokens_delivered == 3 * len(ref)
    assert life.tokens_delivered_blocks == life.tokens_delivered
    assert snapshot_from_trace(payload).tokens_delivered_blocks == (
        life.tokens_delivered_blocks
    )
    for s in ss:
        assert s.output() == ref
        assert s.output().chunks
        assert all(isinstance(c, np.ndarray) and c.dtype == np.float32
                   for c in s.output().chunks)


@pytest.mark.parametrize("placement", ["host", "host_clip"])
def test_host_fed_sink_counts_no_blocks(placement):
    prog = _placed(_compiled("IDCT8", 32), placement)
    stream = drain_source(prog.graph)
    ss, life, _ = _serve(prog, stream)
    assert life.tokens_delivered == 3 * len(stream)
    assert life.tokens_delivered_blocks == 0
    assert all(isinstance(c, list) for s in ss for c in s.output().chunks)


def test_egress_block_pct_reader():
    def tel(delivered, blocks=None):
        t = SimpleNamespace(tokens_delivered=delivered)
        if blocks is not None:
            t.tokens_delivered_blocks = blocks
        return t

    ctx = SimpleNamespace(tel0=tel(100, 40), tel1=tel(300, 190))
    assert egress_block_pct.read(ctx) == pytest.approx(75.0)
    # a program without the counter, or a window with no delivery
    assert egress_block_pct.read(
        SimpleNamespace(tel0=tel(100), tel1=tel(300))) is None
    assert egress_block_pct.read(
        SimpleNamespace(tel0=tel(100, 0), tel1=tel(100, 0))) is None


def test_egress_block_pct_reads_100_on_a_recorded_serve(tmp_path,
                                                         monkeypatch):
    """The served IDCT8 cell at test size, traced through the benchmark's
    harness: every delivered token reached its buffer as a block."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    result = harness.run(tiny("idct8.serve.streams64"), 2 ** 31 + 11, 1.0,
                         True, time.perf_counter(), platform=None,
                         trace_dir=tmp_path)
    assert result["correct"] is True
    assert result["metrics"]["egress_block_pct.tput"]["value"] == 100.0


# ---------------------------------------------------------------------------
# Checkpoint: a device-fed port's delivered tokens stay numeric
# ---------------------------------------------------------------------------


def test_checkpoint_stores_device_delivered_tokens_as_one_numeric_array(
        tmp_path):
    stream, ref = _reference("IDCT8", 96)
    half = len(stream) // 2
    server = _compiled("IDCT8", 96).serve(start=True)
    s = server.open_session()
    s.submit(stream[:half])
    deadline = time.time() + 60
    while len(s.output()) < half and time.time() < deadline:
        time.sleep(0.01)
    assert len(s.output()) == half
    server.checkpoint(tmp_path)
    server.kill()
    flat, _extra = ckpt.load_flat(tmp_path, ckpt.latest_step(tmp_path))
    stored = flat[f"s{s.sid}/result/sink"]
    assert stored.dtype == np.float32 and stored.shape == (half,)
    assert np.array_equal(stored, np.asarray(ref[:half], np.float32))

    server2 = StreamServer.recover(_compiled("IDCT8", 96), tmp_path,
                                   start=True)
    try:
        s2 = server2.session(s.sid)
        assert isinstance(s2.output().chunks[0], np.ndarray)
        s2.submit(stream[half:])
        s2.close()
        assert server2.drain(timeout=120)
        assert s2.output() == ref
    finally:
        server2.stop()
