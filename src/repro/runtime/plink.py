"""PLink — the partition-link actor (paper §III-D).

Bridges the host software partition and a compiled device partition: it
(1) drains host FIFOs into device-resident blocks (the input-stage burst),
(2) launches the device step asynchronously (JAX async dispatch ≈ OpenCL
out-of-order queue; the returned arrays are futures/events),
(3) writes results back into host FIFOs when ready, and
(4) reads the device idleness flag instead of polling internal state.

PLink is itself an actor on a host thread and never blocks it: if the in-flight
step has not completed (``is_ready`` false), PLink simply yields so other actors
on its thread keep working — the paper's non-blocking OpenCL event design.

DMA/compute overlap: staging packs into a ring of preallocated host buffers
(``_N_SLOTS`` quad-buffering — the packing of launch N+1 reuses a slot whose
launch has long retired, never one still feeding an async dispatch), and up to
``_MAX_INFLIGHT`` launches stay in flight while the next block is packed — the
host-side ``np`` packing of block N+1 genuinely overlaps the device compute of
block N.  Device state never round-trips: each launch is chained off the
previous launch's *state future* (``self.state`` is updated at dispatch time,
not at retirement), the jitted entry donates it, and retirement pulls only the
boundary outputs and the idle flag back to host.  With a megastep program
(``megastep_k > 1``) each launch carries a ``(k, block)`` chunk stack, so the
whole stage→dispatch→sync→retire boundary round-trip is paid once per k
repetition-vector iterations.
"""

from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass

from typing import Any, Deque, Dict, Tuple


import jax
import numpy as np

from repro.runtime import chaos as chaos_mod

from repro.observability.recorder import current as _trace_current
from repro.runtime.device_runtime import DeviceProgram
from repro.runtime.fifo import ArrayFifo

try:
    from ml_dtypes import bfloat16 as _BF16
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    _BF16 = None

_NP_DTYPE = {"float32": np.float32, "int32": np.int32, "float64": np.float64}
if _BF16 is not None:
    _NP_DTYPE["bfloat16"] = _BF16

_warned_dtypes = set()


def reset_dtype_warnings() -> None:
    """Forget which dtypes already warned, so the next offender warns again.

    The warn-once set is module-global (a process should not spam one
    warning per staged block), which makes warn-once *assertions* depend on
    import/execution order.  Tests reset it between cases — see the autouse
    fixture in ``tests/conftest.py``."""
    _warned_dtypes.clear()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned_dtypes:
        _warned_dtypes.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _np_dtype(dt: str):
    """Numpy dtype for a port's token type at the host/device boundary.

    bfloat16 stages as a true bfloat16 buffer (via ml_dtypes) so host-device
    transfers move 2 bytes/token; without ml_dtypes we fall back to float32
    and warn once, because silently widening doubles PCIe traffic and changes
    rounding.  Unknown-but-numeric dtypes resolve through numpy; anything the
    boundary genuinely cannot stage (e.g. ``object``) is rejected at compile
    time by the placement-legalization pass — reaching here with one means a
    hand-built program bypassed the pipeline, so we warn explicitly instead
    of silently miscasting.
    """
    if dt == "bfloat16" and _BF16 is None:  # ml_dtypes missing
        _warn_once(
            "bfloat16",
            "ml_dtypes is not installed: staging bfloat16 channels as "
            "float32 (2x transfer volume, different rounding). "
            "Install ml_dtypes for true bfloat16 host buffers.",
        )
        return np.float32
    if dt in _NP_DTYPE:
        return _NP_DTYPE[dt]
    try:
        resolved = np.dtype(dt)
        if resolved.kind in "fiub":
            return resolved.type
    except TypeError:
        pass
    _warn_once(
        dt,
        f"PLink: channel dtype {dt!r} is not stageable across the "
        f"host/device boundary; falling back to float32. The compile-time "
        f"legalization pass rejects such placements — this program was "
        f"built without it.",
    )
    return np.float32


@dataclass
class PLinkStats:
    launches: int = 0
    tokens_in: int = 0
    tokens_out: int = 0
    idle_signals: int = 0
    # boundary wall-time split (per launch, summed): host-side packing into
    # the staging ring, the async dispatch enqueue, readiness polling on the
    # in-flight results, and the masked write-back into host FIFOs
    stage_ns: int = 0
    dispatch_ns: int = 0
    sync_ns: int = 0
    retire_ns: int = 0
    # legacy aggregates (stage+dispatch / sync+retire) — benchmark compat
    h2d_ns: int = 0
    d2h_ns: int = 0
    tests: int = 0  # scheduler profiling contract


# Staging ring depth and in-flight launch cap.  _N_SLOTS > _MAX_INFLIGHT + 1
# guarantees the slot being packed is never one a still-in-flight launch may
# read (the jit argument path can alias the numpy staging buffer zero-copy
# on CPU):
# the busy-slot skip in ``_stage_inputs`` enforces it structurally.
_N_SLOTS = 4
_MAX_INFLIGHT = 2


class PLink:
    """Host-side controller for one device partition.

    Duck-types the actor-machine `invoke` contract so the scheduler treats it as
    a normal actor on its thread (the paper schedules PLink on p1).
    """

    # PLink paints its own lane track (stage/dispatch/sync/retire spans);
    # the scheduler must not double-paint its invokes as actor spans.
    trace_self = True

    def __init__(self, program: DeviceProgram, env, name: str = "plink"):
        self.program = program
        self.env = env  # PortEnv: host FIFO endpoints for the boundary ports
        self.name = name
        self.state = program.fresh_state()  # donated by the first launch
        self.stats = PLinkStats()
        self.k = max(1, program.megastep_k)
        # streamtrace: recorder captured once at construction — the invoke
        # hot path pays one attribute read + None check when tracing is off.
        # Readiness polls accumulate into _sync_acc and flush as ONE sync
        # span per retire, so the event count stays O(launches) while the
        # span totals still match PLinkStats exactly.
        self.recorder = _trace_current()
        self._track = f"lane:{name}"
        self._sync_acc = 0
        self._sync_t0 = 0
        # in-flight launches, oldest first: (outs, idle, n_in, slot).  The
        # state future is NOT kept here — it was chained (and donated) into
        # the next launch at dispatch time, so readiness polling must never
        # touch it: its buffer may already be consumed.
        self.inflight: Deque[Tuple[Dict, Any, int, int]] = deque()
        self.pending_valid: Dict[str, int] = {}
        self.terminated = False
        self.device_idle = False
        # minimal Actor-duck for the scheduler
        self.actor = type("A", (), {"name": name})()
        self.stats_tests = 0
        # preallocated staging ring: per slot, per boundary port, one
        # (k, block) value buffer + mask reused across launches
        shape = (self.k, program.block)
        self._slots = [
            {
                f"{a}.{p}": (
                    np.zeros(shape, _np_dtype(dt)),
                    np.zeros(shape, bool),
                )
                for (a, p, dt) in program.in_ports
            }
            for _ in range(_N_SLOTS)
        ]
        self._slot = 0

    # -- helpers ---------------------------------------------------------------
    def _phase(self, name: str, t0_ns: int, dur_ns: int, **args) -> None:
        """One boundary-phase span on this lane's track."""
        rec = self.recorder
        if rec is not None:
            rec.complete(self._track, name, "plink", t0_ns, dur_ns, args)

    def _flush_sync(self) -> None:
        """Emit accumulated readiness-poll time as a single sync span."""
        if self._sync_acc:
            self._phase("sync", self._sync_t0, self._sync_acc)
            self._sync_acc = 0

    def _plan(self, closed=()) -> Dict[str, int]:
        """Tokens stageable per boundary port right now: whole staging
        granules, lane-aligned across each destination actor's ports (a
        lockstep pair like a MAC's XIN/AIN must never skew — with
        device→device lanes the producing PLink runs on another thread, so
        per-port counts are not snapshot-atomic), capped at one block;
        the port groups in ``closed`` stage nothing."""
        block = self.program.block
        quanta = self.program.in_quanta
        plan: Dict[str, int] = {}
        for group, keys in self.program.in_groups.items():
            if group in closed:
                continue
            g = min(
                min(self.env.inputs[k].count(), block) // quanta[k]
                for k in keys
            )
            if g > 0:
                for k in keys:
                    plan[k] = g * quanta[k]
        return plan

    def _stage_inputs(self):
        """Drain host FIFOs into the next free staging-ring slot.

        One ``(k, block)`` chunk stack per boundary port (a plain
        ``(block,)`` row when ``k == 1``), packed into *preallocated* reused
        buffers — no per-launch allocation churn.  Chunks are planned one at
        a time (``_plan`` re-runs between rows), which drains the FIFOs in
        exactly the order k sequential one-block launches would; every
        position not written this launch is zeroed with its mask False, so a
        reused buffer can never leak a previous launch's tokens into the
        padding a stateful scan walks over.  Bulk drains go through the
        FIFO's low-copy ``peek_view``/``commit`` window when the ring
        storage is contiguous, falling back to ``read``.  When the
        program's state is carried by stream ops, a port group that stages
        a short chunk stages nothing more this launch — a producer on
        another thread may refill its FIFO meanwhile — so each port's valid
        tokens stay a prefix of the launch.
        """
        device = self.program.device
        # Only a non-default device needs an explicit transfer: the jitted
        # step's committed state pins placement, so uncommitted numpy slot
        # buffers ride the jit argument fast path (~5x cheaper than a
        # device_put round per launch on this backend).  The staging ring's
        # busy-slot discipline makes that safe — a slot is never rewritten
        # while its launch is still in flight, so even a zero-copy alias of
        # the numpy buffer is stable until the launch retires.
        put = (
            None if device is None or device is jax.devices()[0]
            else (lambda tree: jax.device_put(tree, device))
        )
        t0 = time.perf_counter_ns()
        busy = {s for (_o, _i, _n, s) in self.inflight}
        idx = self._slot
        while idx in busy:
            idx = (idx + 1) % _N_SLOTS
        slot = self._slots[idx]
        total = 0
        closed = set()
        for j in range(self.k):
            plan = self._plan(closed)
            any_n = False
            for (a, p, _dt) in self.program.in_ports:
                key = f"{a}.{p}"
                arr, mask = slot[key]
                n = plan.get(key, 0)
                if n:
                    any_n = True
                    ep = self.env.inputs[key]
                    view = (
                        ep.peek_view(n)
                        if hasattr(ep, "peek_view") else None
                    )
                    if view is not None:
                        arr[j, :n] = np.asarray(view, dtype=arr.dtype)
                        ep.commit(n)
                    else:
                        arr[j, :n] = np.asarray(ep.read(n), dtype=arr.dtype)
                arr[j, n:] = 0
                mask[j, :n] = True
                mask[j, n:] = False
                total += n
            if self.program.state_slots:
                closed.update(
                    group
                    for group, keys in self.program.in_groups.items()
                    if any(plan.get(k, 0) < self.program.block for k in keys)
                )
            if not any_n and j + 1 < self.k:
                # out of stageable granules: the remaining chunks are pure
                # padding (zero values, all-False masks) — static (k, block)
                # shapes mean one jit trace serves every fill level
                for arr, mask in slot.values():
                    arr[j + 1:] = 0
                    mask[j + 1:] = False
                break
        staged = {}
        for (a, p, _dt) in self.program.in_ports:
            key = f"{a}.{p}"
            arr, mask = slot[key]
            if self.k == 1:
                staged[key] = (arr[0], mask[0])
            else:
                staged[key] = (arr, mask)
        # one batched transfer for the whole pytree when a transfer is
        # needed at all: per-leaf dispatches collapse into a single call —
        # the fixed dispatch cost dominates at block scale, and on a
        # GIL-bound host every µs the PLink thread spends dispatching is
        # stolen from the interpreted actors
        if put is not None:
            staged = put(staged)
        dt_ns = time.perf_counter_ns() - t0
        self.stats.stage_ns += dt_ns
        self.stats.h2d_ns += dt_ns
        self._phase("stage", t0, dt_ns, tokens=total, k=self.k)
        return staged, total, idx

    def _retire(self, outs, idle) -> int:
        """Pull one completed launch's *boundary* outputs back to host —
        never internal FIFO or actor state, which stays device-resident."""
        t0 = time.perf_counter_ns()
        moved = 0
        # one batched D2H pull for every output leaf instead of a sync
        # transfer per port
        outs = jax.device_get(outs)
        for key, (vals, mask) in outs.items():
            # (k, block) boolean indexing flattens row-major = chunk order,
            # so megastep outputs retire in exactly per-iteration order
            keep = vals[mask]
            if keep.size:
                # the endpoint decides the storage: a device->device
                # ArrayFifo queues the array itself; a RingFifo carries host
                # tokens, boxed via tolist() — native Python floats, not
                # numpy scalars, so downstream interpreted actors do native
                # arithmetic instead of paying ~10x per-token on np.float32
                ep = self.env.outputs[key]
                if isinstance(getattr(ep, "fifo", None), ArrayFifo):
                    ep.write(keep)
                else:
                    ep.write(keep.tolist())
                moved += int(keep.size)
        self.device_idle = bool(idle)
        if self.device_idle:
            self.stats.idle_signals += 1
        dt_ns = time.perf_counter_ns() - t0
        self.stats.retire_ns += dt_ns
        self.stats.d2h_ns += dt_ns
        self.stats.tokens_out += moved
        self._phase("retire", t0, dt_ns, tokens=moved, idle=self.device_idle)
        return moved

    # -- scheduler contract ------------------------------------------------------
    @property
    def pending(self) -> bool:
        """True while a device step is in flight — the scheduler must not
        declare quiescence until the step retires (its outputs may wake
        downstream actors)."""
        return len(self.inflight) > 0

    def invoke(self, max_execs: int = 1) -> int:
        progress = 0
        # 1) retire completed launches, oldest first, without blocking.
        # Readiness polls only the boundary outputs + idle flag — the state
        # future was donated into the chained next launch and must not be
        # touched here.
        while self.inflight:
            outs, idle, _n_in, _slot = self.inflight[0]
            t0 = time.perf_counter_ns()
            arrays = jax.tree.leaves((outs, idle))
            ready = all(
                a.is_ready() for a in arrays if hasattr(a, "is_ready")
            )
            poll_ns = time.perf_counter_ns() - t0
            self.stats.sync_ns += poll_ns
            self.stats.d2h_ns += poll_ns
            if self.recorder is not None:
                if not self._sync_acc:
                    self._sync_t0 = t0
                self._sync_acc += poll_ns
            if not ready:
                if len(self.inflight) >= _MAX_INFLIGHT:
                    return progress  # pipeline full; never block (§III-D)
                break  # head still computing — overlap: stage the next block
            self.inflight.popleft()
            self._flush_sync()
            progress += self._retire(outs, idle)
        # 2) stage + launch the next block while up to _MAX_INFLIGHT - 1
        # earlier launches compute (DMA/compute overlap).  Never launch a
        # step whose retirement could overflow an output FIFO: every launch
        # still in flight may retire up to k*block valid tokens per port,
        # and a device->device lane (or a slow host consumer) has no other
        # backpressure point — the lane would assert mid-retire.  Space can
        # only grow between launch and retire (this PLink is the single
        # writer), so checking before staging is sufficient; the check also
        # runs before _stage_inputs so no host tokens are drained into a
        # block we then refuse to launch.
        has_inputs = bool(self.program.in_ports)
        if has_inputs and not self._plan():
            # nothing stageable: return before touching the staging ring —
            # idle polls while a launch computes must not pay the (k, block)
            # buffer zeroing that _stage_inputs does per call
            return progress
        need = (len(self.inflight) + 1) * self.k * self.program.block
        for ep in self.env.outputs.values():
            cap = getattr(getattr(ep, "fifo", None), "capacity", None)
            if cap is not None and ep.space() < min(need, cap):
                return progress
        # chaos site BEFORE staging: an injected lane death leaves the
        # host FIFOs untouched (no tokens drained into a launch that will
        # never happen) — the failure surfaces through the scheduler as a
        # run error, never as silent token loss
        chaos_mod.poke(f"plink:{self.name}")
        staged, n_in, slot = self._stage_inputs()
        if n_in == 0 and has_inputs:
            return progress
        t0 = time.perf_counter_ns()
        state, outs, idle = self.program.launch(self.state, staged)
        # chain the NEXT launch off this launch's state *future* — state
        # never round-trips to host, and the jitted entry donates it
        self.state = state
        dt_ns = time.perf_counter_ns() - t0
        self.stats.dispatch_ns += dt_ns
        self.stats.h2d_ns += dt_ns
        self._phase("dispatch", t0, dt_ns, tokens=n_in, k=self.k)
        self.inflight.append((outs, idle, n_in, slot))
        self._slot = (slot + 1) % _N_SLOTS
        self.stats.launches += 1
        self.stats.tokens_in += n_in
        progress += n_in
        return progress

    @property
    def stats_obj(self):
        return self.stats
