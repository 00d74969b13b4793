"""Fault-tolerant StreamServe: deterministic chaos injection, per-session
checkpoint/restore (kill-and-recover bit-identity on every Table-I network),
bounded launch retry, graceful degradation to the all-host placement,
per-session blast-radius isolation, and the checkpoint-layer hardening
(AsyncCheckpointer error surfacing, torn-write invisibility)."""

import time

import jax
import numpy as np
import pytest

import repro
from repro import checkpoint as ckpt
from repro.apps.streams import NETWORKS
from repro.checkpoint import AsyncCheckpointer
from repro.runtime import chaos
from repro.serve_stream import DeviceCompileError, ServeError, StreamServer

BLOCK = 256

SIZES = {
    "TopFilter": 1200,
    "FIR32": 600,
    "Bitonic8": 48,
    "IDCT8": 48,
    "ZigZag": 9,
}
EGRESS = {"FIR32": "sink"}  # FIR also has the x-forward xsink


def drain_source(graph, name="source"):
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


def _build(name, size):
    builder = NETWORKS[name]
    return builder(size) if name != "FIR32" else builder(n=size)


def _reference(name, size):
    net, got = _build(name, size)
    prog = repro.compile(net, backend="device", block=BLOCK)
    stream = drain_source(prog.graph)
    prog.run()
    return stream, list(got)


def _compiled(name, size, **kw):
    net, _ = _build(name, size)
    return repro.compile(net, backend="device", block=BLOCK, **kw)


# ---------------------------------------------------------------------------
# chaos: the deterministic injection layer itself
# ---------------------------------------------------------------------------


def test_chaos_rule_parse_roundtrip():
    c = chaos.parse("launch:*|at=2,5;actor:f@s0|after=3;plink:*|p=0.25", seed=9)
    assert [r.spec() for r in c.rules] == [
        "launch:*|at=2,5", "actor:f@s0|after=3", "plink:*|p=0.25",
    ]
    assert c.seed == 9
    # coerce() accepts a controller, a spec string, a rule list, and None
    assert chaos.coerce(c) is c
    assert chaos.coerce(None) is None
    assert chaos.coerce("launch:*|at=1").rules[0].at == (1,)
    assert chaos.coerce([chaos.FaultRule("ckpt:*", after=2)]).rules[0].after == 2
    with pytest.raises(ValueError):
        chaos.parse("launch:*|frobnicate=1")


def test_chaos_occurrence_triggers_are_deterministic():
    """p-rules are a pure function of (seed, site, n): two controllers with
    the same seed inject at identical occurrence indices, a different seed
    gives a different (but still reproducible) schedule."""

    def schedule(seed):
        c = chaos.Chaos([chaos.FaultRule("x:*", p=0.3)], seed=seed)
        hits = []
        for i in range(200):
            try:
                c.poke("x:a")
            except chaos.InjectedFault:
                hits.append(i)
        return hits

    a, b = schedule(7), schedule(7)
    assert a == b and len(a) > 10
    assert schedule(8) != a


def test_chaos_at_after_and_delay():
    c = chaos.Chaos([
        chaos.FaultRule("launch:p0", at=(2,)),
        chaos.FaultRule("actor:*", after=3),
        chaos.FaultRule("plink:*", at=(1,), delay_s=0.05),
    ])
    c.poke("launch:p0")
    with pytest.raises(chaos.InjectedLaunchFailure):
        c.poke("launch:p0")
    c.poke("launch:p0")  # at= is exact, not persistent
    c.poke("actor:f@s0")
    c.poke("actor:f@s0")
    for _ in range(3):  # after= is a dead lane: every occurrence >= 3 fails
        with pytest.raises(chaos.InjectedActorFailure):
            c.poke("actor:f@s0")
    t0 = time.perf_counter()
    c.poke("plink:dev0")  # delay rules stall instead of raising
    assert time.perf_counter() - t0 >= 0.05
    assert c.occurrences("launch:p0") == 3
    assert [h[0] for h in c.hits] == [
        "launch:p0", "actor:f@s0", "actor:f@s0", "actor:f@s0", "plink:dev0",
    ]


def test_scheduler_mode_actor_site_fires():
    """Program.run() (not serve): the thread scheduler's per-partition
    actor site injects and the fault propagates as a run error."""
    net, _ = _build("TopFilter", 600)
    prog = repro.compile(net, backend="host", block=BLOCK)
    rule = chaos.FaultRule("actor:filter@*", at=(1,))
    with chaos.activate(chaos.Chaos([rule])):
        with pytest.raises(chaos.InjectedActorFailure):
            prog.run()


def test_plink_lane_site_fires_before_staging():
    """An injected lane death in scheduler mode surfaces as a run error —
    and because the site fires before ``_stage_inputs``, no host FIFO was
    drained into the launch that never happened."""
    net, _ = _build("TopFilter", 600)
    prog = repro.compile(net, backend="device", block=BLOCK)
    with chaos.activate(chaos.Chaos([chaos.FaultRule("plink:*", at=(1,))])):
        with pytest.raises(chaos.InjectedLaneDeath):
            prog.run()


def test_chaos_env_activation(monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS", "launch:*|at=1")
    monkeypatch.setenv("CHAOS_SEED", "42")
    c = chaos.from_env()
    assert c is not None and c.seed == 42
    assert chaos.current() is None
    with chaos.activate(c):
        assert chaos.current() is c
        with pytest.raises(chaos.InjectedLaunchFailure):
            chaos.poke("launch:dev0")
    assert chaos.current() is None
    chaos.poke("launch:dev0")  # no controller installed: free


# ---------------------------------------------------------------------------
# tentpole 1: kill-and-recover bit-identity on every Table-I network
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NETWORKS))
def test_kill_and_recover_bitwise(name, tmp_path):
    size = SIZES[name]
    stream, ref = _reference(name, size)
    half = len(stream) // 2

    server = _compiled(name, size).serve(start=True)
    s = server.open_session()
    if half:
        s.submit(stream[:half])
    if half >= 2 * BLOCK:  # big streams: checkpoint after real delivery
        deadline = time.time() + 60
        while s.first_delivery_ns is None and time.time() < deadline:
            time.sleep(0.005)
        assert s.first_delivery_ns is not None
    path = server.checkpoint(tmp_path)
    assert path.exists()
    server.kill()  # no shutdown flush — simulates an engine crash

    server2 = StreamServer.recover(_compiled(name, size), tmp_path, start=True)
    try:
        rep = server2.recovery
        assert rep is not None and not rep.sessions[0].finished
        assert rep.sessions[0].replay_bound >= 0
        s2 = server2.session(0)
        s2.submit(stream[half:])
        s2.close()
        assert server2.drain(timeout=120)
        assert s2.output(EGRESS.get(name)) == ref  # bitwise
    finally:
        server2.stop()


def test_recover_reports_replay_bound_and_restored_delivery(tmp_path):
    stream, ref = _reference("TopFilter", 1200)
    server = _compiled("TopFilter", 1200).serve(start=True)
    s = server.open_session()
    s.submit(stream[:600])
    deadline = time.time() + 60
    while s.first_delivery_ns is None and time.time() < deadline:
        time.sleep(0.005)
    server.checkpoint(tmp_path)
    server.kill()

    server2 = StreamServer.recover(_compiled("TopFilter", 1200), tmp_path)
    rep = server2.recovery
    sr = rep.sessions[0]
    assert sr.delivered_restored > 0          # results survived the kill
    assert sr.replay_bound == sr.queued_tokens + sr.in_pipeline_tokens
    assert rep.replayed_tokens_bound == sr.replay_bound
    assert rep.step == 1
    # the restored session must not re-observe TTFO for replayed blocks
    s2 = server2.session(0)
    assert s2.first_delivery_ns is not None
    server2.start()
    try:
        s2.submit(stream[600:])
        s2.close()
        assert server2.drain(timeout=120)
        assert s2.output() == ref
    finally:
        server2.stop()


def test_recover_rejects_wrong_network_and_missing_checkpoint(tmp_path):
    with pytest.raises(ServeError, match="no complete checkpoint"):
        StreamServer.recover(_compiled("IDCT8", 48), tmp_path)
    server = _compiled("IDCT8", 48).serve()
    server.checkpoint(tmp_path)  # engine not started: inline snapshot
    with pytest.raises(ServeError, match="network"):
        StreamServer.recover(_compiled("ZigZag", 9), tmp_path)


def test_recover_drr_state_dropped_for_finished_sessions(tmp_path):
    """A session that finished before the checkpoint must not leave stale
    sids in the restored deficit-round-robin state, and its buffered output
    must still be readable after recovery."""
    stream, ref = _reference("IDCT8", 48)
    server = _compiled("IDCT8", 48).serve(start=True)
    done = server.open_session()
    done.submit(stream)
    done.close()
    assert done.join(timeout=60)
    live = server.open_session()
    live.submit(stream[: len(stream) // 2])
    server.checkpoint(tmp_path)
    server.kill()

    server2 = StreamServer.recover(_compiled("IDCT8", 48), tmp_path)
    assert server2.recovery.sessions[done.sid].finished
    sched_sids = set(server2._sched._last_round) | set(server2._sched._served)
    assert done.sid not in sched_sids  # no stale DRR entries
    d2, l2 = server2.session(done.sid), server2.session(live.sid)
    assert d2.output() == ref  # finished session restored verbatim
    server2.start()
    try:
        l2.submit(stream[len(stream) // 2:])
        l2.close()
        assert server2.drain(timeout=120)
        assert l2.output() == ref
        assert server2._next_sid > live.sid  # fresh sids never collide
    finally:
        server2.stop()


def test_periodic_checkpointing_recovers_from_last_complete_step(tmp_path):
    """checkpoint_every_s: the engine snapshots on its own clock; after a
    kill, recovery comes from whatever step completed last."""
    stream, ref = _reference("TopFilter", 1200)
    server = _compiled("TopFilter", 1200).serve(
        start=True, checkpoint_dir=tmp_path, checkpoint_every_s=0.05,
    )
    s = server.open_session()
    s.submit(stream[:600])
    deadline = time.time() + 60
    while ckpt.latest_step(tmp_path) is None and time.time() < deadline:
        time.sleep(0.01)
    assert ckpt.latest_step(tmp_path) is not None
    server.kill()

    server2 = StreamServer.recover(_compiled("TopFilter", 1200), tmp_path,
                                   start=True)
    try:
        s2 = server2.session(0)
        s2.submit(stream[600:])
        s2.close()
        assert server2.drain(timeout=120)
        assert s2.output() == ref
    finally:
        server2.stop()


# ---------------------------------------------------------------------------
# tentpole 2+3: injected faults — retry, degradation, blast radius
# ---------------------------------------------------------------------------


def test_transient_launch_fault_retried_bitwise():
    """One injected launch failure: the bounded retry replays the identical
    round (the chaos site fires before staging, so no tokens were drained)
    and the stream completes bit-identically with zero degradation."""
    stream, ref = _reference("TopFilter", 1200)
    prog = _compiled("TopFilter", 1200)
    with prog.serve(chaos="launch:*|at=2") as server:
        s = server.open_session()
        s.submit(stream)
        s.close()
        assert server.drain(timeout=120)
        assert s.output() == ref  # bitwise despite the mid-stream fault
        assert server.chaos.hits  # the fault actually fired
        assert server._c_faults.value >= 1
        assert server._c_recoveries.value >= 1
        assert server._g_degraded.value == 0
        assert not server._quarantined
        text = server.metrics_text()
        assert "serve_faults_total" in text
        assert "serve_recoveries_total" in text


def test_persistent_launch_failure_degrades_to_host():
    """Every launch fails: the partition exhausts its retry budget, is
    quarantined, and sessions hot-swap to the all-host placement — outputs
    stay bit-identical (host == hetero is the conformance invariant)."""
    stream, ref = _reference("TopFilter", 1200)
    prog = _compiled("TopFilter", 1200)
    with prog.serve(chaos="launch:*|after=1", launch_retries=2,
                    retry_base_s=0.001) as server:
        s = server.open_session()
        s.submit(stream)
        s.close()
        assert server.drain(timeout=120)
        assert s.output() == ref
        assert server._quarantined  # the lane is out of rotation
        assert server._g_degraded.value == 1
        assert server.program.hw_partition is None  # now all-host
        assert server.telemetry.lifetime().swaps == 1


def _break_launches(monkeypatch, min_lanes):
    """Make every batched launch of ``min_lanes`` or more lanes fail the
    way a kernel that cannot lower does: at its first call, with no token
    staged into it yet."""
    from repro.runtime.device_runtime import DeviceProgram

    def broken(orig):
        def get(self, batch):
            fn = orig(self, batch)

            def call(state, ins):
                if jax.tree.leaves(ins)[0].shape[0] >= min_lanes:
                    raise ValueError("Mosaic failed to compile TPU kernel")
                return fn(state, ins)

            return call

        return get

    for name in ("batched_step", "batched_megastep"):
        monkeypatch.setattr(
            DeviceProgram, name, broken(getattr(DeviceProgram, name))
        )


def test_compile_error_fails_serve_and_never_degrades(monkeypatch):
    """A device launch that cannot compile fails serve() itself: it is not
    a launch fault, so it is never retried, counted or degraded to host."""
    _break_launches(monkeypatch, min_lanes=1)
    prog = _compiled("TopFilter", 1200)
    with pytest.raises(DeviceCompileError, match="failed to compile"):
        prog.serve()


def test_compile_error_at_a_new_batch_width_stops_the_engine(monkeypatch):
    """A width first needed mid-service compiles before any session rides
    it; when that fails the engine stops and every client sees the error —
    the sessions are not carried on to the host placement."""
    _break_launches(monkeypatch, min_lanes=2)
    stream, _ref = _reference("TopFilter", 1200)
    server = _compiled("TopFilter", 1200).serve()  # width 1 compiles fine
    sessions = [server.open_session() for _ in range(3)]
    for s in sessions:
        s.submit(stream)
        s.close()
    server.start()  # the first round has three ready lanes
    with pytest.raises(ServeError, match="failed to compile"):
        server.drain(timeout=120)
    with pytest.raises(DeviceCompileError):
        server.stop()
    for s in sessions:
        with pytest.raises(ServeError):
            s.output()
    assert server._c_faults.value == 0
    assert server._g_degraded.value == 0
    assert not server._quarantined
    assert server.program.hw_partition is not None  # never swapped to host


def test_lane_death_mid_service_degrades_and_completes():
    """The PLink-site variant: the lane dies after some healthy launches
    (tokens already flowed through the device), then every later launch
    fails — degradation must carry the in-flight residue to the host
    placement without loss or reorder."""
    stream, ref = _reference("TopFilter", 2000)
    prog = _compiled("TopFilter", 2000)
    with prog.serve(chaos="launch:*|after=2", launch_retries=1,
                    retry_base_s=0.001) as server:
        s = server.open_session()
        s.submit(stream)
        s.close()
        assert server.drain(timeout=120)
        out = s.output()
        assert len(out) == len(ref)
        assert out == ref
        assert server._g_degraded.value == 1


def test_actor_fault_isolated_to_one_session():
    """One session's actor raising must fail THAT session (traceback
    captured, output() raises) while the engine keeps serving the others —
    the blast-radius fix for the engine-wide ``except BaseException``."""
    net, got = _build("TopFilter", 1200)
    prog = repro.compile(net, backend="host", block=BLOCK)
    stream = drain_source(prog.graph)
    prog.run()
    ref = list(got)
    net2, _ = _build("TopFilter", 1200)
    prog2 = repro.compile(net2, backend="host", block=BLOCK)
    with prog2.serve(chaos="actor:*@s0|at=1") as server:
        s0 = server.open_session()
        s1 = server.open_session()
        for s in (s0, s1):
            s.submit(stream)
            s.close()
        assert server.drain(timeout=120)
        assert s1.output() == ref          # the healthy session is untouched
        assert s0.error is not None
        assert "InjectedActorFailure" in s0.error  # traceback captured
        with pytest.raises(ServeError):
            s0.output()
        assert server._c_faults.value >= 1
        # the engine itself survived: a NEW session still completes
        s2 = server.open_session()
        s2.submit(stream)
        s2.close()
        assert server.drain(timeout=120)
        assert s2.output() == ref


def test_chaos_knob_accepts_controller_and_records_hits():
    c = chaos.Chaos([chaos.FaultRule("launch:*", at=(1,))], seed=3)
    stream, ref = _reference("IDCT8", 48)
    prog = _compiled("IDCT8", 48)
    with prog.serve(chaos=c) as server:
        assert server.chaos is c
        s = server.open_session()
        s.submit(stream)
        s.close()
        assert server.drain(timeout=120)
        assert s.output() == ref
    assert [h[0].startswith("launch:") for h in c.hits] == [True]


# ---------------------------------------------------------------------------
# checkpoint-layer hardening (satellites)
# ---------------------------------------------------------------------------


def test_torn_checkpoint_write_is_invisible(tmp_path):
    """A save killed mid-write (leaf or commit) leaves ``latest`` at the
    previous complete step, no torn step dir, and no temp litter."""
    tree = {"a": np.arange(4, dtype=np.float32), "b": np.ones(3)}
    ckpt.save(tmp_path, 1, tree)
    assert ckpt.latest_step(tmp_path) == 1
    for step, rule in ((2, chaos.FaultRule("ckpt:leaf", at=(2,))),
                       (3, chaos.FaultRule("ckpt:commit", at=(1,)))):
        with chaos.activate(chaos.Chaos([rule])):
            with pytest.raises(chaos.InjectedCheckpointFailure):
                ckpt.save(tmp_path, step, tree)
        assert ckpt.latest_step(tmp_path) == 1      # restore point intact
        assert not (tmp_path / f"step_{step}").exists()
        assert not list(tmp_path.glob(".tmp_*"))    # no litter
    restored, _ = ckpt.restore(tmp_path, 1, tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), tree["a"])


def test_latest_marker_is_never_read_half_written(tmp_path):
    """The ``latest`` commit marker is replaced atomically: a reader polling
    it while saves land (the periodic serve checkpoint) never sees it empty,
    and the step it names only moves forward."""
    import threading

    tree = {"a": np.arange(4, dtype=np.float32)}
    n_saves = 300
    done = threading.Event()

    def writer():
        try:
            for step in range(1, n_saves + 1):
                ckpt.save(tmp_path, step, tree)
        finally:
            done.set()

    t = threading.Thread(target=writer)
    t.start()
    seen = []
    while not done.is_set():
        step = ckpt.latest_step(tmp_path)  # raised on an empty marker
        if step is not None:
            seen.append(step)
    t.join()
    assert seen == sorted(seen)
    assert ckpt.latest_step(tmp_path) == n_saves


def test_async_checkpointer_surfaces_background_error(tmp_path):
    """A background save failure is never silent: it re-raises on the next
    save() or wait(), whichever comes first — and is then cleared so the
    checkpointer keeps working."""
    tree = {"x": np.ones(2, dtype=np.float32)}
    acp = AsyncCheckpointer(tmp_path)
    with chaos.activate(chaos.Chaos([chaos.FaultRule("ckpt:commit", at=(1,))])):
        acp.save(1, tree)
        with pytest.raises(chaos.InjectedCheckpointFailure):
            acp.wait()  # surfaces on wait()
    assert ckpt.latest_step(tmp_path) is None  # torn step is invisible
    acp.close()

    acp2 = AsyncCheckpointer(tmp_path)
    with chaos.activate(chaos.Chaos([chaos.FaultRule("ckpt:commit", at=(1,))])):
        acp2.save(1, tree)
        acp2._q.join()  # background failure recorded, not yet surfaced
        with pytest.raises(chaos.InjectedCheckpointFailure):
            acp2.save(2, tree)  # surfaces on the NEXT save()
    acp2.save(2, tree)  # error cleared: the checkpointer still works
    acp2.wait()
    assert ckpt.latest_step(tmp_path) == 2
    acp2.close()


def test_object_dtype_leaves_roundtrip_exact_types(tmp_path):
    """Pickled object leaves (the serve recovery path's token streams) must
    round-trip exact Python/NumPy scalar types — bit-identity depends on
    it (np.float32 + float promotion differs from float64 math)."""
    toks = [np.float32(1.5), float(2.25), np.int32(3), True]
    arr = np.empty(len(toks), dtype=object)
    for i, v in enumerate(toks):
        arr[i] = v
    ckpt.save(tmp_path, 1, {"toks": arr, "num": np.arange(3)})
    flat, _ = ckpt.load_flat(tmp_path, 1)
    back = flat["toks"].tolist()
    assert back == toks
    assert [type(v) for v in back] == [type(v) for v in toks]
    assert flat["num"].dtype == np.arange(3).dtype


def test_simulated_failure_joins_chaos_taxonomy():
    from repro.distributed.fault import SimulatedFailure

    e = SimulatedFailure("boom")
    assert isinstance(e, chaos.InjectedFault)
    assert isinstance(e, RuntimeError)
    assert e.site == "train:step"
