"""Continuous batched device dispatch — a rolling batch, one launch per round.

The sequential path costs one XLA dispatch (and one Pallas launch inside
each fused region) *per session per block*.  The batcher packs the staged
blocks of many sessions into a single ``DeviceProgram`` launch: lanes are
vmapped, so each session's lane is bit-identical to its own sequential
dispatch while the launch overhead is paid once.

Unlike the original drain-per-block batcher (power-of-two buckets, each
session riding at most one in-flight batch), dispatch is *continuous*:

  * **rolling rounds** — sessions join and leave the batch at block
    boundaries without draining the in-flight set.  A session's device
    state is never round-tripped to host between rounds: each launch
    immediately rebinds ``stage.state`` to that lane's slice of the
    launch's output-state *future*, so the same session can ride the very
    next round while the previous one is still computing — XLA chains the
    launches through the state dependency.  Retire only moves *outputs*
    back to host FIFOs, oldest round first, preserving per-session order.
  * **ragged lane packing** — a round's batch width is the live lane
    count, not a power-of-two bucket.  When reusing an already-compiled
    width saves a retrace (within ``LANE_SLACK`` waste), the round is
    padded with *masked* lanes — init state, all-False masks, outputs
    discarded — instead of duplicating the last real lane's state and
    payload.  jit caches one specialization per width actually used,
    bounded by ``max_batch``.
  * **fairness** — the engine hands ``launch`` a fairness-ordered stage
    list (``serve_stream.admission.DeficitRoundRobin``); everything past
    ``max_batch`` waits for the next round and the rotation guarantees it
    gets one.
  * **sequential mode** — ``mode="sequential"`` dispatches one launch per
    session instead; it exists as the benchmark baseline
    (``benchmarks/server_throughput.py``) and a debugging aid.  State
    chaining works the same way, so even sequential sessions ride
    back-to-back launches.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.observability.recorder import span
from repro.serve_stream.session import DeviceCompileError, DeviceStage

# A round may be padded with masked lanes up to this factor over the live
# lane count when that reuses an already-compiled width — bounds wasted
# lanes at ~1/3 (the power-of-two buckets it replaces wasted up to 2x,
# *and* computed a duplicated real lane instead of a masked no-op).
LANE_SLACK = 4 / 3


def _tree_ready(tree) -> bool:
    return all(
        getattr(a, "is_ready", lambda: True)()
        for a in jax.tree.leaves(tree)
        if hasattr(a, "is_ready")
    )


@dataclass
class _Round:
    """One in-flight launch: ``riders`` are the real lanes (lane index ==
    list position); padded mask-only lanes are never retired."""

    riders: List[DeviceStage]
    outs: Dict                         # {port: (vals, mask)} — batched or not
    width: int                         # launch width (>= len(riders))
    batched: bool
    t_launch_ns: int = 0


class DeviceBatcher:
    """Owns every in-flight device dispatch of one ``StreamServer``."""

    def __init__(
        self,
        program,
        *,
        mode: str = "continuous",   # "continuous" | "sequential"
        max_batch: int = 32,
        depth: int = 2,             # in-flight rounds (double buffering)
        telemetry=None,
        recorder=None,
        chaos=None,
    ):
        if mode == "batched":       # legacy alias for the rolling batcher
            mode = "continuous"
        if mode not in ("continuous", "sequential"):
            raise ValueError(f"DeviceBatcher mode {mode!r}")
        self.program = program
        self.mode = mode
        self.max_batch = max(1, max_batch)
        self.depth = max(1, depth)
        self.telemetry = telemetry
        self.recorder = recorder  # streamtrace (None = untraced server)
        self.chaos = chaos        # fault injection (None = no chaos)
        self._track = "batch:" + (
            getattr(program, "partition", "") or program.name
        )
        self.inflight: List[_Round] = []
        self._widths: set = set()  # batch widths already traced
        self._pad_payload = None   # zero (vals, mask) arrays, built lazily

    # -- width selection ------------------------------------------------------
    def _width(self, live: int) -> int:
        """Smallest already-compiled width within ``LANE_SLACK`` of the live
        lane count, else exactly the live count (compiled now)."""
        cap = min(math.ceil(live * LANE_SLACK), self.max_batch)
        reuse = [w for w in self._widths if live <= w <= cap]
        if reuse:
            return min(reuse)
        self.prepare(live)
        return live

    def prepare(self, width: int = 1) -> None:
        """Compile the ``width``-lane launch before any session rides it, by
        launching one round of padding lanes (init state, all-False masks)
        and waiting for it.  A compile or lowering failure raises
        ``DeviceCompileError``: it is a property of the program, so the
        engine never retries it, counts it as a fault or degrades around it.
        Sequential mode launches one lane per session, so only width 1
        exists there."""
        width = 1 if self.mode == "sequential" else width
        if width in self._widths:
            return
        program = self.program
        try:
            if self.mode == "sequential":
                ins = self._on_device({
                    k: (jnp.asarray(v), jnp.asarray(m))
                    for k, (v, m) in self._pad().items()
                })
                out = program.launch(program.fresh_state(), ins)
            else:
                fn = (
                    program.batched_megastep(width)
                    if getattr(program, "megastep_k", 1) > 1
                    else program.batched_step(width)
                )
                out = fn(
                    program.stack_states([program.init_state] * width),
                    self._on_device(
                        program.pack_lanes([self._pad()] * width)
                    ),
                )
                # the state handling a launch of this width runs, too:
                # lanes go out of the launch and ride the next one
                lanes = program.unstack_states(out[0], width)
                out = program.stack_states(lanes)
            jax.block_until_ready(out)
        except Exception as e:
            raise DeviceCompileError(
                f"device partition {self._track[len('batch:'):]!r}: the "
                f"{width}-lane launch failed to compile: {e!r}"
            ) from e
        self._widths.add(width)

    def _pad(self) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
        """The masked no-op payload one pad lane contributes: zeros with an
        all-False mask, so the vmapped step treats the lane as dead work."""
        if self._pad_payload is None:
            from repro.runtime.plink import _np_dtype

            k = max(1, getattr(self.program, "megastep_k", 1))
            shape = (
                (k, self.program.block) if k > 1 else (self.program.block,)
            )
            self._pad_payload = {
                f"{a}.{p}": (
                    np.zeros(shape, _np_dtype(dt)),
                    np.zeros(shape, bool),
                )
                for (a, p, dt) in self.program.in_ports
            }
        return self._pad_payload

    def _on_device(self, tree):
        """Inputs onto the partition's bound device.  A stateless partition
        has no committed state to pin its launch, so without this every
        lane would run on the default device whatever its PE names."""
        device = self.program.device
        if device is None or device is jax.devices()[0]:
            return tree
        return jax.device_put(tree, device)

    def _dispatched(self, sp: span, lanes: int, tokens_in: int,
                    width: int, blocks: int) -> None:
        """Feed one dispatch to telemetry and the same numbers to the
        launch span's args, so replay is exact."""
        if self.telemetry is not None:
            self.telemetry.device_dispatched(lanes, tokens_in, width=width,
                                             blocks=blocks)
        sp.args.update(lanes=lanes, tokens_in=tokens_in, width=width,
                       blocks=blocks)

    def _span(self, name: str, round_no: int) -> span:
        return span(
            self.recorder, self._track, "batcher", name, cat="device",
            round=round_no,
        )

    # -- launch --------------------------------------------------------------
    def can_launch(self) -> bool:
        return len(self.inflight) < self.depth

    def launch(self, stages: List[DeviceStage], round_no: int = 0) -> int:
        """Dispatch one round over up to ``max_batch`` of ``stages`` (in the
        given order — the engine's fairness ordering); returns lanes
        launched.  Stages already riding an earlier round may join: their
        state is the previous round's output future and XLA serializes the
        launches through it.  ``round_no`` is the engine round the launch
        span belongs to."""
        if self.chaos is not None:
            # chaos site BEFORE any staging: an injected launch failure
            # leaves every FIFO and stage untouched, so the engine's
            # bounded retry replays the identical round with zero token
            # loss (docs/reliability.md)
            self.chaos.poke(
                "launch:"
                + (getattr(self.program, "partition", "")
                   or self.program.name)
            )
        if self.mode == "sequential":
            return self._launch_sequential(stages, round_no)
        with self._span("launch", round_no) as sp:
            payloads = []
            live: List[DeviceStage] = []
            for st in stages:
                if len(live) >= self.max_batch:
                    break
                staged = st.stage()
                if staged is not None:
                    payloads.append(staged)
                    live.append(st)
            if not live:
                sp.discard()
                return 0
            t0 = time.perf_counter_ns()
            tokens = sum(
                int(m.sum()) for p in payloads for _, m in p.values()
            )
            width = self._width(len(live))
            padded = payloads + [self._pad()] * (width - len(live))
            states = [st.state for st in live]
            states += [self.program.init_state] * (width - len(live))
            state_b = self.program.stack_states(states)
            ins_b = self._on_device(self.program.pack_lanes(padded))
            batched_fn = (
                self.program.batched_megastep(width)
                if getattr(self.program, "megastep_k", 1) > 1
                else self.program.batched_step(width)
            )
            state_b, outs, _idle = batched_fn(state_b, ins_b)
            lanes = self.program.unstack_states(state_b, width)
            for st, lane_state in zip(live, lanes):
                # rebind each rider to its lane's output-state future so it
                # can ride the NEXT round before this one retires
                st.state = lane_state
                st.inflight += 1
            entry = _Round(live, outs, width=width, batched=True)
            self.inflight.append(entry)
            self._dispatched(sp, len(live), tokens, width,
                             sum(st.blocks_staged for st in live))
            entry.t_launch_ns = time.perf_counter_ns() - t0
        return len(live)

    def _launch_sequential(
        self, stages: List[DeviceStage], round_no: int
    ) -> int:
        """One dispatch, and one launch span, per session — the per-session
        baseline.  ``program.launch`` routes to the megastep when the
        program runs k>1 iterations per dispatch (payloads are (k, block)
        chunk stacks)."""
        launched = 0
        for st in stages:
            if launched >= self.max_batch:
                break
            with self._span("launch", round_no) as sp:
                staged = st.stage()
                if staged is None:
                    sp.discard()
                    continue
                t0 = time.perf_counter_ns()
                tokens = sum(int(m.sum()) for _, m in staged.values())
                ins = self._on_device({
                    k: (jnp.asarray(v), jnp.asarray(m))
                    for k, (v, m) in staged.items()
                })
                state, outs, _idle = self.program.launch(st.state, ins)
                st.state = state  # the donated chain: next launch feeds here
                st.inflight += 1
                entry = _Round([st], outs, width=1, batched=False)
                self.inflight.append(entry)
                self._dispatched(sp, 1, tokens, 1, st.blocks_staged)
                entry.t_launch_ns = time.perf_counter_ns() - t0
            launched += 1
        return launched

    # -- retire --------------------------------------------------------------
    def poll(self, block: bool = False, round_no: int = 0) -> int:
        """Retire completed rounds (oldest first, preserving per-session
        order); ``block=True`` forces the oldest to completion.  Returns
        tokens moved back into host FIFOs."""
        moved = 0
        while self.inflight:
            head = self.inflight[0]
            if not block and not _tree_ready(head.outs):
                break
            moved += self._retire(head, round_no)
            self.inflight.pop(0)
            block = False  # only force the oldest
        return moved

    def _retire(self, entry: _Round, round_no: int) -> int:
        with self._span("retire", round_no) as sp:
            moved = 0
            if entry.batched:
                outs_np = {
                    k: (np.asarray(v), np.asarray(m))
                    for k, (v, m) in entry.outs.items()
                }
                for lane, st in enumerate(entry.riders):
                    lane_outs = {
                        k: (v[lane], m[lane]) for k, (v, m) in outs_np.items()
                    }
                    moved += st.retire(lane_outs)
            else:
                (st,) = entry.riders
                moved += st.retire(entry.outs)
            # telemetry's device time is this retire plus the launch call's
            # wall time; args.time_ns carries that same value so replay
            # matches device_time_ns exactly, while the span itself shows
            # the host-side retire work
            time_ns = time.perf_counter_ns() - sp.t0_ns + entry.t_launch_ns
            if self.telemetry is not None:
                self.telemetry.device_retired(moved, time_ns)
            sp.args.update(
                tokens_out=moved, lanes=len(entry.riders), time_ns=time_ns
            )
        return moved

    # -- introspection -------------------------------------------------------
    @property
    def pending(self) -> bool:
        return bool(self.inflight)

    def drain(self, round_no: int = 0) -> int:
        """Force-retire everything in flight (poll only forces the oldest)."""
        moved = 0
        while self.inflight:
            moved += self.poll(block=True, round_no=round_no)
        return moved
