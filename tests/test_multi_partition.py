"""Multi-partition device runtime: N concurrent accelerator partitions.

Covers the tentpole end to end: legalization of k-way device placements,
one fused region per partition in the IR dump, per-partition PLink lanes
(device→device channels over numpy ``ArrayFifo`` lane pairs), bitwise
equivalence of 2-partition placements against the single-partition and
host paths through both ``Program.run()`` and ``Program.serve()``, the
exhaustive small-N placement sweep, multi-lane serving with a mid-stream
single↔multi hot-swap, the multi-accelerator MILP/DSE surface, and the
``runtime_from_xcf`` unknown-code-generator fix.
"""

import numpy as np
import pytest

import repro
from repro.apps.streams import NETWORKS
from repro.core.graph import GraphError
from repro.core.xcf import make_xcf
from repro.runtime.device_runtime import resolve_pe_device
from repro.runtime.fifo import ArrayFifo
from repro.runtime.scheduler import runtime_from_xcf

from helpers import drain_source

BLOCK = 64


# ---------------------------------------------------------------------------
# placement enumeration helpers
# ---------------------------------------------------------------------------


def _eligible(graph):
    return [a for a in graph.topo_order() if graph.actors[a].device_ok]


def _reach(graph, seeds, forward=True):
    edges = {}
    for ch in graph.channels:
        a, b = (ch.src, ch.dst) if forward else (ch.dst, ch.src)
        edges.setdefault(a, set()).add(b)
    out, work = set(), list(seeds)
    while work:
        n = work.pop()
        for m in edges.get(n, ()):
            if m not in out:
                out.add(m)
                work.append(m)
    return out


def _convex(graph, group):
    """No path between two members passes through an outside actor — the
    same convexity rule SDF-region detection applies; a non-convex device
    partition would need an internal wire buffered across launches."""
    group = set(group)
    down = _reach(graph, group, forward=True) - group
    up = _reach(graph, group, forward=False) - group
    return not (down & up)


def legal_two_splits(graph, cap=6):
    """Every legal 2-partition split of the device-eligible actors.

    Exhaustive 2-colorings when the eligible set is small; for larger
    networks (Bitonic8's 24 compare-exchangers would be 2^24 colorings)
    every topological prefix cut — still every cut depth, one order.
    Both sides must be non-empty and convex.
    """
    elig = _eligible(graph)
    n = len(elig)
    splits = []
    if n <= cap:
        for bits in range(1, 2 ** n - 1):
            d0 = {elig[i] for i in range(n) if bits & (1 << i)}
            d1 = set(elig) - d0
            if _convex(graph, d0) and _convex(graph, d1):
                splits.append((sorted(d0), sorted(d1)))
    else:
        for k in range(1, n):
            d0, d1 = set(elig[:k]), set(elig[k:])
            if _convex(graph, d0) and _convex(graph, d1):
                splits.append((sorted(d0), sorted(d1)))
    return splits


def split_xcf(graph, d0, d1, host="t0"):
    asg = {}
    d0, d1 = set(d0), set(d1)
    for a in graph.actors:
        asg[a] = "d0" if a in d0 else "d1" if a in d1 else host
    return make_xcf(graph.name, asg, accel=("d0", "d1"))


def _halves(graph):
    """The canonical half/half split used by the equivalence tests."""
    elig = _eligible(graph)
    k = max(1, len(elig) // 2)
    return elig[:k], elig[k:]


# ---------------------------------------------------------------------------
# IR: one fused region per device partition
# ---------------------------------------------------------------------------


def test_ir_one_fused_region_per_partition():
    net, _ = NETWORKS["FIR32"](n=128)
    g = net.graph()
    d0, d1 = _halves(g)
    prog = repro.compile(net, split_xcf(g, d0, d1), block=BLOCK)
    assert prog.hw_partitions == ["d0", "d1"]
    mod = prog.module
    hw_of = mod.hw_assignment()
    fused = [n for n, a in mod.actors.items() if a.is_fused]
    # exactly one fused actor per device partition, and fusion never
    # crossed the partition boundary
    assert sorted(hw_of[f] for f in fused) == ["d0", "d1"]
    for f in fused:
        members = set(mod.actors[f].fused_from)
        assert members <= (set(d0) if hw_of[f] == "d0" else set(d1))
    # the dump tells the same story per pass
    dump = prog.ir_dump("fuse-sdf-regions")
    assert "region d0 [hw/" in dump and "region d1 [hw/" in dump


def test_device_to_device_channel_is_staged_lane_pair():
    net, _ = NETWORKS["FIR32"](n=128)
    g = net.graph()
    d0, d1 = _halves(g)
    prog = repro.compile(net, split_xcf(g, d0, d1), block=BLOCK)
    rt = prog._build_runtime()
    lanes = [f for f in rt.fifos.values() if isinstance(f, ArrayFifo)]
    # the systolic (x, acc) pair crosses d0 -> d1 as two numpy lanes
    assert len(lanes) == 2
    # each partition has its own PLink on its own scheduler thread
    assert sorted(rt.plinks) == ["d0", "d1"]
    assert {rt.plinks[p].program.partition for p in rt.plinks} == {"d0", "d1"}
    threads_of_plinks = {
        part.name
        for part in rt.partitions.values()
        for inst in part.instances
        if inst in rt.plinks.values()
    }
    assert len(threads_of_plinks) == 2  # independent lanes pipeline


def test_resolve_pe_device():
    import jax

    default = jax.devices()[0]
    assert resolve_pe_device("") is None
    assert resolve_pe_device("x86_64") is None
    assert resolve_pe_device("tpu-v5e-16x16") is default
    plat = default.platform
    assert resolve_pe_device(f"{plat}:0") is default
    # a PE naming an absent device raises, listing what is present — no
    # modulo wrap onto another chip, no fallback to another platform
    n = len([d for d in jax.devices() if d.platform == plat])
    with pytest.raises(GraphError, match=f"{plat} device {n}.*{default}"):
        resolve_pe_device(f"{plat}:{n}")
    missing = "gpu" if plat != "gpu" else "tpu"
    with pytest.raises(GraphError, match=f"{missing} device 0"):
        resolve_pe_device(missing)
    # compiled programs carry the binding
    net, _ = NETWORKS["IDCT8"](8)
    prog = repro.compile(net, backend="device", block=BLOCK)
    dp = prog.device_program()
    assert dp.pe == "tpu-v5e-16x16"
    assert dp.device is default


# ---------------------------------------------------------------------------
# Acceptance: FIR32 + ZigZag, 2 partitions == 1 partition == host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,size", [("FIR32", 256), ("ZigZag", 6)])
def test_two_partition_run_bitwise(name, size):
    net, got = (
        NETWORKS[name](n=size) if name == "FIR32" else NETWORKS[name](size)
    )
    g = net.graph()
    repro.compile(net, backend="host").run()
    host = list(got)
    repro.compile(net, backend="device", block=BLOCK).run()
    single = list(got)
    d0, d1 = _halves(g)
    xcf = split_xcf(g, d0, d1)
    for fuse in (True, False):
        repro.compile(net, xcf, block=BLOCK, fuse=fuse).run()
        assert list(got) == single  # bitwise vs the single-partition path
    np.testing.assert_allclose(single, host, rtol=1e-5, atol=1e-4)
    if name == "ZigZag":  # integer-exact ops: bitwise across everything
        assert single == host


@pytest.mark.parametrize("name,size", [("FIR32", 256), ("ZigZag", 6)])
def test_two_partition_serve_bitwise(name, size):
    net, got = (
        NETWORKS[name](n=size) if name == "FIR32" else NETWORKS[name](size)
    )
    g = net.graph()
    d0, d1 = _halves(g)
    prog = repro.compile(net, split_xcf(g, d0, d1), block=BLOCK)
    stream = drain_source(g)
    prog.run()
    ref = list(got)
    with prog.serve(batching=True) as server:
        sessions = [server.open_session() for _ in range(2)]
        for s in sessions:
            s.submit(stream)
            s.close()
        assert server.drain(timeout=120)
        for s in sessions:
            assert s.output("sink") == ref  # bitwise, via the serve path


# ---------------------------------------------------------------------------
# Satellite: exhaustive small-N placement sweep
# ---------------------------------------------------------------------------

SWEEP = [
    ("TopFilter", dict(n=64)),
    ("FIR32", dict(taps=4, n=64)),
    ("Bitonic8", dict(n_vectors=4)),
    ("IDCT8", dict(n_blocks=4)),
    ("ZigZag", dict(n_blocks=2)),
]


@pytest.mark.parametrize("name,kw", SWEEP, ids=[s[0] for s in SWEEP])
def test_placement_sweep_exhaustive(name, kw):
    """Every legal 2-partition device split of each Table-I network (plus
    ZigZag) golden-checks against the host reference."""
    builder = NETWORKS[name]
    net, got = builder(**kw)
    g = net.graph()
    splits = legal_two_splits(g)
    if not splits:  # TopFilter: one device-eligible actor, nothing to split
        assert len(_eligible(g)) < 2
        pytest.skip(f"{name}: fewer than two device-eligible actors")
    repro.compile(net, backend="host").run()
    host = list(got)
    assert host
    for d0, d1 in splits:
        prog = repro.compile(net, split_xcf(g, d0, d1), block=64)
        prog.run()
        out = list(got)
        assert len(out) == len(host), (d0, d1)
        np.testing.assert_allclose(
            out, host, rtol=1e-5, atol=1e-4, err_msg=f"split {d0} | {d1}"
        )


# ---------------------------------------------------------------------------
# Satellite: multi-lane serving equivalence + single<->multi hot-swap
# ---------------------------------------------------------------------------


def test_serving_multi_lane_staggered_equals_sequential():
    """B staggered sessions over a 2-device-partition XCF, bitwise equal to
    B sequential ``Program.run()`` streams."""
    sizes = [4, 6, 5]
    refs, streams = [], []
    for sz in sizes:
        net, got = NETWORKS["ZigZag"](sz)
        prog = repro.compile(net, backend="device", block=BLOCK)
        streams.append(drain_source(prog.graph))
        prog.run()
        refs.append(list(got))

    net, _ = NETWORKS["ZigZag"](sizes[0])
    g = net.graph()
    prog = repro.compile(net, split_xcf(g, *_halves(g)), block=BLOCK)
    with prog.serve(batching=True) as server:
        sessions = [server.open_session() for _ in sizes]
        offsets = [0] * len(sessions)
        chunks = [96, 160, 64]
        while any(o < len(st) for o, st in zip(offsets, streams)):
            for i, s in enumerate(sessions):
                if offsets[i] < len(streams[i]):
                    c = streams[i][offsets[i]:offsets[i] + chunks[i % 3]]
                    s.submit(c)
                    offsets[i] += len(c)
        for s in sessions:
            s.close()
        assert server.drain(timeout=120)
        for s, ref in zip(sessions, refs):
            assert s.output() == ref  # bitwise
        t = server.telemetry.lifetime()
    assert t.device_lanes > t.device_dispatches  # batching actually shared


def test_serving_hot_swap_between_single_and_multi_partition():
    """A session stream survives a mid-stream hot-swap from a
    single-partition XCF to a 2-partition one and back, bit-identically."""
    net, got = NETWORKS["ZigZag"](9)
    g = net.graph()
    prog = repro.compile(net, backend="device", block=BLOCK)
    stream = drain_source(g)
    prog.run()
    ref = list(got)

    single_xcf = prog.xcf
    multi_xcf = split_xcf(g, *_halves(g))
    third = len(stream) // 3

    def wait_swaps(server, n, timeout=60.0):
        import time

        deadline = time.perf_counter() + timeout
        while len(server.telemetry.swap_log) < n:
            assert time.perf_counter() < deadline, "swap never landed"
            time.sleep(0.005)

    with prog.serve(batching=True) as server:
        s = server.open_session()
        s.submit(stream[:third])
        server.request_repartition(multi_xcf)  # single -> multi
        wait_swaps(server, 1)  # requests coalesce; let the first land
        s.submit(stream[third:2 * third])
        server.request_repartition(single_xcf)  # multi -> single
        wait_swaps(server, 2)
        s.submit(stream[2 * third:])
        s.close()
        assert server.drain(timeout=120)
        assert s.output() == ref  # no token lost, dropped, or reordered
        assert server.program.xcf is single_xcf
        assert len(server.telemetry.swap_log) == 2


# ---------------------------------------------------------------------------
# Satellite fix: runtime_from_xcf rejects unknown code generators
# ---------------------------------------------------------------------------


def test_runtime_from_xcf_rejects_unknown_code_generator():
    net, _ = NETWORKS["TopFilter"](64)
    g = net.graph()
    xcf = make_xcf(g.name, {a: "p0" for a in g.actors})
    xcf.partitions["p0"].code_generator = "systemc"
    with pytest.raises(GraphError) as e:
        runtime_from_xcf(g, xcf)
    msg = str(e.value)
    assert "'p0'" in msg and "systemc" in msg
    assert "hw" in msg and "sw" in msg  # the known generator set, by name


# ---------------------------------------------------------------------------
# DSE: explore() emits multi-partition design points
# ---------------------------------------------------------------------------


def test_explore_emits_multi_partition_points():
    net, _ = NETWORKS["IDCT8"](16)
    prog = repro.compile(net, block=128)
    prof = prog.profile(block=128, include_links=False)
    points = prog.explore(
        prof, thread_counts=(1,), accel_options=(0, 1, 2), accel_capacity=2
    )
    by_accels = {p.n_accels: p for p in points}
    assert set(by_accels) == {0, 1, 2}
    two = by_accels[2]
    used = {
        pid for pid in two.solution.assignment.values()
        if pid in two.accel_ids
    }
    # capacity=2 cannot fit all three device actors in one partition
    assert len(used) == 2
    hw_parts = [
        p for p in two.xcf.partitions.values() if p.code_generator == "hw"
    ]
    assert len(hw_parts) == 2
    for spec in hw_parts:
        assert 0 < len(spec.instances) <= 2
    # the emitted XCF compiles and runs through the ordinary pipeline
    placed = prog.repartition(xcf=two.xcf)
    assert len(placed.hw_partitions) == 2
    r = placed.run()
    assert r.fires > 0 and r.plink_launches > 0
