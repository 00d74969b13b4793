"""Megastep launches: k repetition-vector iterations per device dispatch.

Covers the persistent device-resident streaming contract:

  * k resolution ("auto"/int/False) and per-partition clamping (stateful
    regions, shallow crossing FIFOs, no-input partitions),
  * megastep ≡ per-iteration, bitwise, on every Table-I network and on
    both megastep lowerings (flat Pallas grid / lax.scan),
  * donated-state discipline: state futures chain launch-to-launch and a
    donated tree is never read again host-side,
  * staging-buffer reuse (PLink ring + serve-mode DeviceStage),
  * the stage/dispatch/sync/retire boundary-stats split,
  * serve(): megastep placements hot-swap mid-stream without loss, and
    batched megastep lanes match sequential runs bitwise.
"""

import time

import jax
import numpy as np
import pytest

import repro
from repro.analysis import run_streamcheck
from repro.apps.streams import NETWORKS
from repro.core.actor import simple_actor, sink_actor, source_actor
from repro.core.graph import ActorGraph
from repro.core.xcf import ConnectionSpec, make_xcf
from repro.frontend.program import synthesize_xcf
from repro.ir.passes import DEFAULT_MEGASTEP_K, lower, resolve_megastep

BLOCK = 64

SIZES = {  # small per-network workloads: enough for several megastep launches
    "TopFilter": 900,
    "FIR32": 600,
    "Bitonic8": 48,
    "IDCT8": 48,
    "ZigZag": 9,
}


def _build(name, size):
    builder = NETWORKS[name]
    return builder(size) if name != "FIR32" else builder(n=size)


def _chain_graph(n_tok=600, stateful=False):
    """source -> dev (device-eligible) -> sink, integer-exact values."""
    g = ActorGraph("mega")

    def gen(stt):
        i = stt.get("i", 0)
        if i >= n_tok:
            return stt, None
        return {"i": i + 1}, float(i % 7 - 3)

    g.add(source_actor("source", gen,
                       has_next=lambda stt: stt.get("i", 0) < n_tok))
    if stateful:
        # running sum: small ints stay exact in float32, so host float64
        # and device float32 agree bitwise
        def fn(stt, v):
            acc = stt.get("acc", 0.0) + v
            return {"acc": acc}, acc

        g.add(simple_actor("dev", fn, state={"acc": 0.0}))
    else:
        g.add(simple_actor("dev", lambda stt, v: (stt, v * 2.0 + 1.0)))
    got = []
    g.add(sink_actor("sink", lambda stt, v: (got.append(float(v)), stt)[1]))
    g.connect("source", "dev")
    g.connect("dev", "sink")
    xcf = make_xcf(g.name, {"source": "t0", "dev": "accel", "sink": "t0"})
    return g, got, xcf


# ---------------------------------------------------------------------------
# k resolution + clamping
# ---------------------------------------------------------------------------


def test_resolve_megastep_values():
    assert resolve_megastep(None) == 1
    assert resolve_megastep(False) == 1
    assert resolve_megastep("auto") == DEFAULT_MEGASTEP_K
    assert resolve_megastep(3) == 3
    assert resolve_megastep(0) == 1  # floor at 1


def test_megastep_k_on_compiled_partitions():
    net, _ = _build("FIR32", 400)
    p = repro.compile(net, backend="device", block=BLOCK, megastep=3)
    (prog,) = p.device_programs().values()
    assert prog.megastep_k == 3
    assert prog.flat_megastep  # FIR fuses to one Pallas stream region
    assert prog.megastep is not None and prog.raw_megastep is not None
    # megastep disabled: classic one-block step only
    p1 = repro.compile(net, backend="device", block=BLOCK, megastep=False)
    (prog1,) = p1.device_programs().values()
    assert prog1.megastep_k == 1 and prog1.megastep is None


def test_stateful_partition_clamps_to_one():
    g, _got, xcf = _chain_graph(stateful=True)
    p = repro.compile(g, xcf, block=BLOCK, megastep=4)
    (prog,) = p.device_programs().values()
    # the block scan advances actor state over padding positions, so only
    # all-stateless partitions keep megastep ≡ per-iteration on ragged tails
    assert prog.megastep_k == 1


def test_shallow_crossing_fifo_clamps_k():
    g, _got, xcf = _chain_graph()
    # pin both crossing FIFOs to 2 blocks: floor(k) = depth // (2*block) = 1
    xcf.connections.append(
        ConnectionSpec("source", "OUT", "dev", "IN", 2 * BLOCK))
    xcf.connections.append(
        ConnectionSpec("dev", "OUT", "sink", "IN", 2 * BLOCK))
    p = repro.compile(g, xcf, block=BLOCK, megastep=4, check="warn")
    (prog,) = p.device_programs().values()
    assert prog.megastep_k == 1
    # ... and streamcheck names the clamp (SB206, warning not error)
    diags = [d for d in p.check() if d.code == "SB206"]
    assert diags and all(d.severity == "warning" for d in diags)


def test_inferred_depths_scale_with_k_so_no_sb206():
    g, _got, xcf = _chain_graph()
    mod = lower(g, xcf, block=BLOCK, megastep=4)
    assert mod.meta["megastep"] == 4
    for ch in mod.channels:
        assert ch.resolved_depth >= 2 * 4 * BLOCK
    assert not [d for d in run_streamcheck(mod, block=BLOCK)
                if d.code == "SB206"]


# ---------------------------------------------------------------------------
# bitwise: megastep == per-iteration on every Table-I network
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NETWORKS))
@pytest.mark.parametrize("k", [2, 5])
def test_megastep_bitwise_per_iteration(name, k):
    net, got = _build(name, SIZES[name])
    repro.compile(net, backend="device", block=BLOCK, megastep=False).run()
    ref = list(got)
    got.clear()
    p = repro.compile(net, backend="device", block=BLOCK, megastep=k)
    p.run()
    assert got == ref, (name, k, got[:8], ref[:8])


def test_megastep_bitwise_unfused_scan_path(k=3):
    """fuse=False forces the lax.scan megastep (per-actor step body)."""
    name = "FIR32"
    net, got = _build(name, 400)
    repro.compile(net, backend="device", block=BLOCK, fuse=False,
                  megastep=False).run()
    ref = list(got)
    got.clear()
    p = repro.compile(net, backend="device", block=BLOCK, fuse=False,
                      megastep=k)
    (prog,) = p.device_programs().values()
    assert prog.megastep_k == k and not prog.flat_megastep
    p.run()
    assert got == ref


def test_stateful_chain_pipelined_launches_bitwise():
    """Launch-time state chaining under donation: a stateful device actor
    (k clamps to 1, but launches still pipeline 2-deep) must produce the
    host stream exactly."""
    g, got, _xcf = _chain_graph(n_tok=2000, stateful=True)
    repro.compile(g, backend="host").run()
    ref = list(got)
    got.clear()
    g2, got2, xcf2 = _chain_graph(n_tok=2000, stateful=True)
    repro.compile(g2, xcf2, block=BLOCK).run()
    assert got2 == ref


# ---------------------------------------------------------------------------
# donated-state discipline
# ---------------------------------------------------------------------------


def test_donated_state_is_never_read_after_donation():
    g, _got, xcf = _chain_graph(stateful=True)
    p = repro.compile(g, xcf, block=BLOCK)
    (prog,) = p.device_programs().values()
    assert prog.donate

    def ins(v=1.0):
        vals = np.full((BLOCK,), v, np.float32)
        mask = np.ones((BLOCK,), bool)
        return {"dev.IN": (vals, mask)}

    st1, outs1, _ = prog.step(prog.init_state, ins())
    # chain: st1's tree is donated into the second launch
    st2, outs2, _ = prog.step(st1, ins())
    np.asarray(outs2["dev.OUT"][0])  # force completion
    if jax.default_backend() != "cpu":
        # on accelerators donation really deletes the buffer: reading the
        # donated tree must raise, proving no host-side alias survives
        with pytest.raises(RuntimeError):
            np.asarray(jax.tree.leaves(st1)[0])
    # the chained state is live and correct either way
    assert np.asarray(jax.tree.leaves(st2)[0]).shape == ()


def test_each_launch_chain_starts_from_its_own_state_copy():
    """The donated chain never reuses a tree: a second run() and every new
    serve session start from their own device copy of ``init_state``, and
    no state leaf is ever handed to two launches (on an accelerator the
    first launch deletes what it was given)."""
    g, got, xcf = _chain_graph(n_tok=300, stateful=True)
    p = repro.compile(g, xcf, block=BLOCK)
    (prog,) = p.device_programs().values()
    init_leaves = jax.tree.leaves(prog.init_state)
    assert init_leaves and all(isinstance(x, jax.Array) for x in init_leaves)
    handed = []  # state leaves of every launch, kept alive so ids stay unique
    step = prog.step

    def recording_step(state, ins):
        handed.append(jax.tree.leaves(state))
        return step(state, ins)

    prog.step = recording_step
    p.run()
    first = list(got)
    first_launch_of_run2 = len(handed)
    got.clear()
    p.run()
    assert got == first
    assert first_launch_of_run2 < len(handed)
    run_leaves = [leaf for leaves in handed for leaf in leaves]
    server = p.serve(batching=False)  # sequential lanes donate like PLink
    sessions = [server.open_session() for _ in range(2)]
    stage_leaves = [
        leaf for s in sessions for st in s.pipeline.stages.values()
        for leaf in jax.tree.leaves(st.state)
    ]
    stream = [float(i % 7 - 3) for i in range(300)]
    with server:
        for s in sessions:
            s.submit(stream)
            s.close()
        assert server.drain(timeout=120)
    assert all(s.output() == first for s in sessions)

    launched = [leaf for leaves in handed for leaf in leaves]
    assert len({id(x) for x in launched}) == len(launched)
    for leaf in launched + stage_leaves:
        assert not any(leaf is x for x in init_leaves)
    assert len({id(x) for x in stage_leaves}) == len(stage_leaves)
    assert not {id(x) for x in stage_leaves} & {id(x) for x in run_leaves}


def test_plink_retire_does_not_touch_state():
    """PLink updates self.state at LAUNCH time (to the async state future)
    and _retire takes only (outs, idle) — writing state at retirement would
    hand an already-donated tree to the next launch."""
    import inspect

    from repro.runtime.plink import PLink

    sig = inspect.signature(PLink._retire)
    assert list(sig.parameters) == ["self", "outs", "idle"]


def test_plink_staging_ring_and_stats_split():
    g, got, xcf = _chain_graph(n_tok=1200)
    p = repro.compile(g, xcf, block=BLOCK, megastep=2)
    rt = p._build_runtime()
    rt.run_threads()
    (plink,) = rt.plinks.values()
    k = plink.program.megastep_k
    assert k == 2
    # quad-buffered staging ring of preallocated (k, block) buffers
    assert len(plink._slots) == 4
    for slot in plink._slots:
        (arr, mask) = slot["dev.IN"]
        assert arr.shape == (k, BLOCK) and mask.shape == (k, BLOCK)
    s = plink.stats
    assert s.launches >= 1200 // (k * BLOCK)
    assert s.stage_ns > 0 and s.dispatch_ns > 0
    # legacy aggregates remain consistent with the split
    assert s.h2d_ns == s.stage_ns + s.dispatch_ns
    assert s.d2h_ns == s.sync_ns + s.retire_ns
    assert len(got) == 1200


def test_device_stage_reuses_staging_buffers():
    from repro.serve_stream.session import DeviceStage

    g, _got, xcf = _chain_graph(n_tok=400)
    p = repro.compile(g, xcf, block=BLOCK, megastep=2)
    (prog,) = p.device_programs().values()
    stage = DeviceStage(prog, p.module)
    from repro.runtime.fifo import RingFifo, ReaderEndpoint, WriterEndpoint

    fin = RingFifo(prog.megastep_k * 2 * BLOCK, "in", deferred=False)
    fout = RingFifo(prog.megastep_k * 2 * BLOCK, "out", deferred=False)
    stage.in_eps["dev.IN"] = ReaderEndpoint(fin)
    stage.out_eps["dev.OUT"] = WriterEndpoint(fout)
    fin.write([float(i) for i in range(BLOCK)])
    pay1 = stage.stage()
    assert pay1 is not None
    assert pay1["dev.IN"][0].shape == (2, BLOCK)
    # everything queued was drained into the buffers: nothing to repack
    assert stage.stage() is None
    state, outs, _ = prog.launch(stage.state, {
        kk: (np.asarray(v), np.asarray(m)) for kk, (v, m) in pay1.items()
    })
    # what the batcher does at launch/retire: rebind the state future,
    # count the in-flight round, then retire outputs only
    stage.state = state
    stage.inflight += 1
    stage.retire(outs)
    assert stage.inflight == 0
    fin.write([float(i) for i in range(BLOCK)])
    pay2 = stage.stage()
    # identical buffer objects: preallocated, reused, not reallocated
    assert pay2["dev.IN"][0] is pay1["dev.IN"][0]
    assert pay2["dev.IN"][1] is pay1["dev.IN"][1]


# ---------------------------------------------------------------------------
# serve(): hot swap + batched lanes under megastep
# ---------------------------------------------------------------------------


def _drain_source(graph, name="source"):
    actor = graph.actors[name]
    action = actor.actions[0]
    state = dict(actor.initial_state)
    out = []
    while action.guard is None or action.guard(state, {}):
        state, produced = action.fire(state, {})
        vals = produced.get(actor.outputs[0].name, [])
        if not vals:
            break
        out.extend(vals)
    return out


def test_serve_batched_megastep_bitwise():
    name = "TopFilter"
    net, got = _build(name, 900)
    prog = repro.compile(net, backend="device", block=BLOCK, megastep=3)
    stream = _drain_source(prog.graph)
    prog.run()
    ref = list(got)
    net2, _ = _build(name, 900)
    prog2 = repro.compile(net2, backend="device", block=BLOCK, megastep=3)
    with prog2.serve(batching=True) as server:
        sessions = [server.open_session() for _ in range(3)]
        for s in sessions:
            s.submit(stream)
            s.close()
        assert server.drain(timeout=120)
        for s in sessions:
            assert s.output() == ref


def test_hot_swap_preserves_megastep_state_bitwise():
    """A mid-stream swap away from (and implicitly back through) a megastep
    device placement must lose nothing and reorder nothing — the transplant
    carries device state across the placement change bit-identically."""
    name = "TopFilter"
    net, got = _build(name, 1200)
    prog = repro.compile(net, backend="device", block=BLOCK, megastep=4)
    stream = _drain_source(prog.graph)
    prog.run()
    ref = list(got)
    net2, _ = _build(name, 1200)
    prog2 = repro.compile(net2, backend="device", block=BLOCK, megastep=4)
    with prog2.serve() as server:
        ss = [server.open_session() for _ in range(2)]
        for s in ss:
            s.submit(stream[:600])
        time.sleep(0.05)  # let tokens flow through the megastep placement
        server.request_repartition(synthesize_xcf(prog2.graph, "host"))
        for s in ss:
            s.submit(stream[600:])
            s.close()
        assert server.drain(timeout=120)
        for s in ss:
            assert s.output() == ref
        assert server.telemetry.lifetime().swaps == 1
