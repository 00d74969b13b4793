#!/usr/bin/env python3
"""Bring-up smoke test: the main path on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: run() and serve() phases
    python chip_smoke.py --chips 4   # four chips: multi-partition phase only

One chip.  Every Table-I network is compiled with ``repro.compile(net,
backend="device", block=4096)`` and run through ``Program.run()`` at a real
stream size, compared with the host reference (``backend="host"``), then run
a second time and compared again (a run must never reuse state an earlier
launch donated).  The float networks also run unfused (``fuse=False``): the
fused kernel must agree with the per-actor path within the same tolerance
(the script says whether the two are bitwise equal).  Then IDCT8 and FIR32
are served to 64 concurrent sessions with staggered submits; every session
must equal a sequential ``run()`` bit for bit, with no fault and no
degradation to the host.

Four chips.  FIR32 split into four device partitions on ``tpu:0..3`` and
IDCT8 into three (it has three device actors) on ``tpu:1..3``; ``run()``
and ``serve()`` must equal the single-partition run bit for bit.

The script stops with a nonzero exit, and prints no result line, unless JAX
finds a TPU; it never carries on on the CPU.  Times and rates it prints are
from that chip run.  The last line of its output is one JSON object naming
the device.  JAX's compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
BLOCK = 4096  # a multiple of the 128 TPU lanes and of ZigZag's 64 tokens

# (network, size argument, tokens): IDCT8 and ZigZag over one 1080p luma
# frame (1920 * 1080 = 2,073,600 samples), the others over 2**21 tokens
RUN_CASES = (
    ("IDCT8", 259_200, 2_073_600),
    ("ZigZag", 32_400, 2_073_600),
    ("FIR32", 2 ** 21, 2 ** 21),
    ("Bitonic8", 2 ** 18, 2 ** 21),
    ("TopFilter", 2 ** 21, 2 ** 21),
)
# integer-valued networks: device and host must agree bit for bit
EXACT = {"ZigZag", "Bitonic8", "TopFilter"}
# float networks: host float64 (and numpy float32 block transforms) against
# device float32 at HIGHEST matmul precision
RTOL, ATOL = 1e-5, 1e-4
EGRESS = {"FIR32": "sink"}  # FIR32 also has the x-forward xsink

SERVE_NETWORKS = ("IDCT8", "FIR32")
SERVE_SESSIONS = 64
SERVE_TOKENS = 8 * BLOCK      # per session: 64 x 32768 = 2**21 tokens
SERVE_CHUNK = BLOCK           # tokens per submit
SERVE_STAGGER = 1             # a new session starts every submit round

MULTI_CASES = (  # network, size argument, tokens, partitions
    ("FIR32", 2 ** 19, 2 ** 19, 4),
    ("IDCT8", 2 ** 16, 2 ** 19, 3),
)
MULTI_SESSIONS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def build(name: str, arg: int):
    from repro.apps.streams import NETWORKS

    return NETWORKS[name](n=arg) if name == "FIR32" else NETWORKS[name](arg)


def source_stream(graph, name: str = "source") -> list:
    """The tokens ``name`` generates — what a serve client submits."""
    action = graph.actors[name].actions[0]
    state = dict(graph.actors[name].initial_state)
    out = []
    while action.guard is None or action.guard(state, {}):
        state, produced = action.fire(state, {})
        vals = next(iter(produced.values()), [])
        if not vals:
            break
        out.extend(vals)
    return out


def first_launch_seconds(dp) -> float:
    """Compile one device program and run one all-padding launch, staged as
    PLink stages (host buffers, moved only to a non-default device)."""
    import jax

    from repro.runtime.plink import _np_dtype

    shape = (dp.megastep_k, dp.block) if dp.megastep_k > 1 else (dp.block,)
    staged = {
        f"{a}.{p}": (np.zeros(shape, _np_dtype(dt)), np.zeros(shape, bool))
        for (a, p, dt) in dp.in_ports
    }
    if dp.device is not None and dp.device is not jax.devices()[0]:
        staged = jax.device_put(staged, dp.device)
    t0 = time.perf_counter()
    jax.block_until_ready(dp.launch(dp.fresh_state(), staged))
    return time.perf_counter() - t0


def compare(name: str, got, ref, what: str = "device - host") -> str:
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        raise AssertionError(f"{name}: {got.shape} tokens, expected {ref.shape}")
    if name in EXACT:
        if not np.array_equal(got, ref):
            raise AssertionError(f"{name}: {what} is not zero")
        return "bitwise equal"
    err = float(np.max(np.abs(got - ref))) if got.size else 0.0
    if not np.allclose(got, ref, rtol=RTOL, atol=ATOL):
        raise AssertionError(
            f"{name}: max |{what}| = {err!r} outside rtol={RTOL} "
            f"atol={ATOL}"
        )
    return f"max |{what}| = {err!r} (rtol={RTOL}, atol={ATOL})"


def run_phase(cases, platform: str) -> None:
    """Each network: host reference, device compile, two device runs."""
    import repro

    log(f"run phase: block={BLOCK}, megastep=auto, tolerance for float "
        f"networks rtol={RTOL} atol={ATOL}")
    for name, arg, tokens in cases:
        net, got = build(name, arg)
        host_report = repro.compile(net, backend="host").run()
        host = list(got)
        prog = repro.compile(net, backend="device", block=BLOCK)
        programs = prog.device_programs()
        compile_s = {pid: first_launch_seconds(dp)
                     for pid, dp in programs.items()}
        for pid, dp in programs.items():
            if dp.device is None or dp.device.platform != platform:
                raise AssertionError(
                    f"{name}/{pid}: bound to {dp.device}, not {platform}"
                )
            log(f"  {name}/{pid}: device={dp.device} k={dp.megastep_k} "
                f"flat_megastep={dp.flat_megastep} "
                f"codegen={sorted(v['codegen'] for v in prog.module.meta.get('fused', {}).values())} "
                f"compile+first launch {compile_s[pid]!r} s")
        outs = []
        for i in (1, 2):
            rep = prog.run()
            outs.append(list(got))
            if rep.plink_launches < 1:
                raise AssertionError(f"{name}: run {i} launched nothing")
            log(f"  {name} run {i}: {len(outs[-1])} tokens out of {tokens} "
                f"in {rep.seconds!r} s = {tokens / rep.seconds!r} tokens/s "
                f"(chip run, end to end with per-token host source and "
                f"sink), plink_launches={rep.plink_launches}, "
                f"{compare(name, outs[-1], host)}")
        if outs[0] != outs[1]:
            raise AssertionError(f"{name}: second run differs from the first")
        log(f"  {name}: host reference {host_report.seconds!r} s; run 2 == "
            f"run 1 bitwise")
        if name not in EXACT:
            unfused = repro.compile(net, backend="device", block=BLOCK,
                                    fuse=False).run()
            same = "bitwise equal" if list(got) == outs[0] else compare(
                name, got, outs[0], "unfused - fused")
            log(f"  {name}: unfused run ({unfused.plink_launches} launches) "
                f"vs fused run: {same}")


def serve_sessions(prog, stream, n_sessions, port, **serve_kw):
    """Serve ``n_sessions`` copies of ``stream`` with staggered starts:
    session i begins submitting at round ``i * SERVE_STAGGER``, so every
    round mixes sessions at different stream positions."""
    chunks = [stream[i:i + SERVE_CHUNK]
              for i in range(0, len(stream), SERVE_CHUNK)]
    with prog.serve(**serve_kw) as server:
        sessions = [server.open_session() for _ in range(n_sessions)]
        t0 = time.perf_counter()
        rounds = (n_sessions - 1) * SERVE_STAGGER + len(chunks)
        for r in range(rounds):
            for i, s in enumerate(sessions):
                c = r - i * SERVE_STAGGER
                if 0 <= c < len(chunks):
                    s.submit(chunks[c])
                    if c == len(chunks) - 1:
                        s.close()
        if not server.drain(timeout=900):
            raise AssertionError("serve: sessions did not finish in 900 s")
        seconds = time.perf_counter() - t0
        outputs = [s.output(port) for s in sessions]
    return server, outputs, seconds


def serve_phase(names, n_sessions, tokens, platform: str) -> None:
    import repro

    log(f"serve phase: {n_sessions} sessions x {tokens} tokens, submits of "
        f"{SERVE_CHUNK} tokens, a new session every {SERVE_STAGGER} rounds")
    for name in names:
        arg = tokens if name == "FIR32" else tokens // 8
        net, got = build(name, arg)
        prog = repro.compile(net, backend="device", block=BLOCK)
        prog.run()
        ref = list(got)
        stream = source_stream(prog.graph)
        serving = repro.compile(build(name, arg)[0], backend="device",
                                block=BLOCK)
        for pid, dp in serving.device_programs().items():
            if dp.device.platform != platform:
                raise AssertionError(f"{name}/{pid}: bound to {dp.device}")
        server, outputs, seconds = serve_sessions(
            serving, stream, n_sessions, EGRESS.get(name)
        )
        bad = [i for i, out in enumerate(outputs) if out != ref]
        if bad:
            raise AssertionError(f"{name}: sessions {bad} differ from run()")
        faults = server.metrics.get("serve_faults_total").value
        degraded = server.metrics.get("serve_degraded").value
        life = server.telemetry.lifetime()
        log(f"  {name}: {n_sessions} sessions bitwise equal to run(); "
            f"{n_sessions * len(stream)} tokens in {seconds!r} s = "
            f"{n_sessions * len(stream) / seconds!r} tokens/s (chip run); "
            f"serve_faults_total={faults!r} serve_degraded={degraded!r} "
            f"device_dispatches={life.device_dispatches} "
            f"mean_batch={life.mean_batch!r}")
        if faults != 0 or degraded != 0:
            raise AssertionError(f"{name}: faults={faults} degraded={degraded}")
        if life.device_dispatches <= 0:
            raise AssertionError(f"{name}: no device dispatch while serving")


def split_xcf(graph, n_parts: int, first_chip: int, platform: str):
    """The network's device actors, in topological order, cut into
    ``n_parts`` contiguous hw partitions on ``<platform>:first_chip...``."""
    from repro.core.xcf import make_xcf

    dev = [a for a in graph.topo_order() if graph.actors[a].device_ok]
    parts = [f"d{i}" for i in range(n_parts)]
    assignment = {a: "t0" for a in graph.actors}
    for i, a in enumerate(dev):
        assignment[a] = parts[i * n_parts // len(dev)]
    xcf = make_xcf(graph.name, assignment, accel=tuple(parts))
    for i, pid in enumerate(parts):
        xcf.partitions[pid].pe = f"{platform}:{first_chip + i}"
    return xcf


def record_output_devices(dp, seen: set) -> None:
    """Wrap a program's launch entry points to record where outputs land."""
    import jax

    def wrap(fn):
        def call(*args):
            out = fn(*args)
            for leaf in jax.tree.leaves(out[1]):
                seen.update(leaf.devices())
            return out
        return call

    dp.launch = wrap(dp.launch)
    for attr in ("batched_step", "batched_megastep"):
        get = getattr(dp, attr)
        setattr(dp, attr, lambda batch, _get=get: wrap(_get(batch)))


def multi_phase(cases, n_sessions, platform: str) -> None:
    import repro

    log(f"multi-partition phase: block={BLOCK}")
    bound = set()
    for name, arg, tokens, n_parts in cases:
        net, got = build(name, arg)
        repro.compile(net, backend="device", block=BLOCK).run()
        single = list(got)
        first_chip = 4 - n_parts
        xcf = split_xcf(net.graph(), n_parts, first_chip, platform)
        prog = repro.compile(net, xcf, block=BLOCK)
        programs = prog.device_programs()
        devices = {dp.device for dp in programs.values()}
        if len(devices) != n_parts or any(
            d.platform != platform for d in devices
        ):
            raise AssertionError(f"{name}: partitions bound to {devices}")
        bound |= devices
        landed = {pid: set() for pid in programs}
        for pid, dp in programs.items():
            record_output_devices(dp, landed[pid])
        rep = prog.run()
        if list(got) != single:
            raise AssertionError(f"{name}: {n_parts}-partition run() differs")
        stream = source_stream(prog.graph)
        _server, outputs, seconds = serve_sessions(
            prog, stream, n_sessions, EGRESS.get(name)
        )
        bad = [i for i, out in enumerate(outputs) if out != single]
        if bad:
            raise AssertionError(f"{name}: sessions {bad} differ from run()")
        for pid, dp in programs.items():
            if landed[pid] != {dp.device}:
                raise AssertionError(
                    f"{name}/{pid}: outputs on {landed[pid]}, bound to "
                    f"{dp.device}"
                )
        log(f"  {name}: {n_parts} partitions on "
            f"{[str(programs[p].device) for p in sorted(programs)]}; run() "
            f"{tokens} tokens in {rep.seconds!r} s, plink_launches="
            f"{rep.plink_launches}, bitwise equal to one partition; serve "
            f"{n_sessions} sessions in {seconds!r} s, bitwise equal; every "
            f"launch's outputs on its partition's chip")
    if len(bound) != 4:
        raise AssertionError(f"bound devices {bound}, expected four chips")
    log(f"  four distinct bound devices: {sorted(str(d) for d in bound)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-partition phase on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    log(f"jax.devices(): {devices}")
    dev0 = devices[0]
    if dev0.platform != "tpu":
        log(f"no TPU found (platform {dev0.platform!r}): this smoke test "
            f"runs only on the chip")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} chips, found "
            f"{len(devices)}")
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime import compile_cache

    log(f"compilation cache: {compile_cache.enable(ROOT / '.jax_cache')}")
    t0 = time.perf_counter()
    if args.chips == 4:
        multi_phase(MULTI_CASES, MULTI_SESSIONS, "tpu")
    else:
        run_phase(RUN_CASES, "tpu")
        serve_phase(SERVE_NETWORKS, SERVE_SESSIONS, SERVE_TOKENS, "tpu")
    log(f"all phases passed in {time.perf_counter() - t0!r} s "
        f"(threads alive: {threading.active_count()})")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
