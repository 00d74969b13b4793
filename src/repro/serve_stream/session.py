"""Client sessions and their per-session pipelines.

A ``StreamSession`` is one client's private stream through the server's
shared compiled ``Program``: a bounded *admission queue* per ingress port
(backpressure: ``submit`` blocks or raises when the queue is full), a
private ``SessionPipeline`` executing the program's host actors over the
session's tokens, and per-egress result buffers.

The pipeline is the serve-mode reading of the lowered module (Fig. 6):

  * **source actors** (no input ports) are *not* instantiated — in serve
    mode the client IS the source, so each source's output channel becomes
    an ingress FIFO pumped from the session's admission queue; a source
    that feeds a device partition gets an ``ArrayFifo`` for both, so the
    client's tokens stay numpy blocks from ``submit`` to the launch's
    staging buffer;
  * **sink actors** (no output ports) are *not* instantiated — their input
    channels become egress FIFOs drained into ``session.output(port)``, a
    ``DeliveredTokens`` buffer; a sink fed by a device partition gets an
    ``ArrayFifo``, so its tokens stay numpy blocks from retire to the
    client;
  * **device actors** are replaced by one ``DeviceStage`` per device
    partition: the PLink lane's stage/retire halves with the launch in the
    middle handed to that partition's shared ``DeviceBatcher``, so B
    sessions' blocks ride one batched dispatch per lane (device→device
    channels between partitions stay numpy blocks in an ``ArrayFifo``);
  * remaining host actors run as ordinary actor machines on the engine
    thread (single-threaded per session, so every FIFO is non-deferred) —
    except fused static-rate regions (``meta["host_fused"]``), whose member
    machines collapse into one block-wise ``HostFusedRegion`` executor per
    session, exactly the one the thread scheduler fires (see
    docs/runtime.md).

Token values take exactly the PLink path (float32 staging, masked write-
back), so a session's outputs are bit-identical to a sequential
``Program.run()`` over the same input stream.
"""

from __future__ import annotations

import array
import bisect
import math
import threading
import time
from typing import Collection, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.actor_machine import ActorMachine, BasicController, PortEnv
from repro.ir.ir import IRModule
from repro.observability.recorder import span
from repro.observability.trace_profile import authored_channel_key
from repro.runtime.fifo import (
    ArrayFifo,
    ReaderEndpoint,
    RingFifo,
    WriterEndpoint,
)
from repro.runtime.plink import _np_dtype


class ServeError(RuntimeError):
    """Invalid use of the streaming server."""


class DeviceCompileError(ServeError):
    """A device partition's launch failed to compile or lower.  Raised to
    the caller; never retried, counted as a fault or degraded to the host."""


class AdmissionFull(ServeError):
    """Non-blocking submit against a full admission queue."""


def device_fed_ingress(module: IRModule) -> Set[str]:
    """Source actors whose channel feeds a device partition.  In serve mode
    their admission queues and ingress FIFOs are ``ArrayFifo``s; every
    other source's are ``RingFifo``s of Python objects, which host actors
    consume."""
    hw_of = module.hw_assignment()
    return {
        ch.src for ch in module.channels
        if ch.dst in hw_of and ch.src not in hw_of
        and not module.actors[ch.src].inputs
    }


def _token_block(values) -> np.ndarray:
    """A submission as one numpy block: a private copy (a client that
    reuses its buffer cannot change queued tokens) with the dtype numpy
    infers, so nothing is rounded before staging casts to the port's
    dtype.  A list of Python floats converts through ``array.array``,
    which gives the same float64 array faster."""
    if type(values) is list and values and type(values[0]) is float:
        try:
            return np.frombuffer(array.array("d", values), np.float64)
        except (TypeError, OverflowError):
            pass  # not all numbers: numpy infers the dtype
    block = np.array(values)
    if block.ndim == 0:  # an iterator rather than a sequence
        block = np.array(list(values))
    return block


def _write_tokens(fifo, tokens) -> None:
    """Write tokens into a FIFO of either kind, converting only where the
    kinds differ: an ``ArrayFifo`` takes numpy blocks (a sequence becomes
    one, as at submit), a ``RingFifo`` Python objects (a block's
    ``tolist()``)."""
    if isinstance(fifo, ArrayFifo):
        if not isinstance(tokens, np.ndarray):
            tokens = _token_block(tokens)
    elif isinstance(tokens, np.ndarray):
        tokens = tokens.tolist()
    fifo.write(tokens)


def _stage_into(row: np.ndarray, part) -> None:
    """Copy one piece of a boundary FIFO into a staging row slice, cast to
    the port's dtype as ``np.asarray(tokens, dtype)`` casts the Python
    scalars the tokens were: host tokens (a list slice) convert one by
    one; an integer block bound for a non-integer row goes through
    float64, as a Python int converts through a C double."""
    if not isinstance(part, np.ndarray):
        row[:] = np.asarray(part, dtype=row.dtype)
    elif part.dtype.kind in "iu" and row.dtype.kind not in "biu":
        row[:] = part.astype(np.float64)
    else:
        row[:] = part


class DeliveredTokens:
    """Tokens delivered on one egress port, kept as the chunks they arrived
    in: numpy blocks from a device-fed sink (no per-token Python object
    between the device and this buffer), lists from a host-fed one.

    Reads like the list it replaces: ``len`` in O(1), an integer index
    returns one token, iteration yields every token, ``==`` compares
    element-wise with any sequence and returns a bool.  A slice and
    ``np.asarray`` return one ndarray (the stored dtype for array chunks)
    built from the chunks without boxing.  One writer (the engine thread)
    appends; a reader on another thread sees a consistent prefix.
    """

    __hash__ = None  # mutable, compared by value

    def __init__(self) -> None:
        self._chunks: List = []  # np.ndarray blocks and lists of tokens
        self._ends: List[int] = []  # cumulative token count per chunk
        self._n = 0

    @property
    def chunks(self) -> List:
        """The stored chunks, oldest first."""
        return self._chunks[:len(self._ends)]

    def extend(self, vals) -> None:
        if isinstance(vals, np.ndarray):
            vals = vals.reshape(-1)
            if not vals.size:
                return
            self._chunks.append(vals)
            self._ends.append(self._n + vals.size)
        else:
            vals = list(vals)
            if not vals:
                return
            if self._chunks and isinstance(self._chunks[-1], list):
                self._chunks[-1].extend(vals)  # host tokens: one list
                self._ends[-1] += len(vals)
            else:
                self._chunks.append(vals)
                self._ends.append(self._n + len(vals))
        self._n = self._ends[-1]

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        for chunk in self.chunks:
            yield from chunk

    def __getitem__(self, key):
        n = self._n
        if isinstance(key, slice):
            lo, hi, step = key.indices(n)
            if step != 1:
                return np.asarray(self)[key]
            return self._span(lo, max(lo, hi))
        i = key + n if key < 0 else key
        if not 0 <= i < n:
            raise IndexError(f"delivered token {key} of {n}")
        k = bisect.bisect_right(self._ends, i)
        return self._chunks[k][i - (self._ends[k - 1] if k else 0)]

    def _span(self, lo: int, hi: int) -> np.ndarray:
        """Tokens ``[lo, hi)`` as one ndarray: one copy, no boxing."""
        ends = self._ends[:]
        parts = []
        k = bisect.bisect_right(ends, lo)
        while lo < hi:
            start = ends[k - 1] if k else 0
            take = min(ends[k], hi) - lo
            parts.append(self._chunks[k][lo - start:lo - start + take])
            lo += take
            k += 1
        if not parts:
            return np.asarray(self._chunks[0][:0] if self._chunks else [])
        return np.concatenate(parts)

    def __array__(self, dtype=None, copy=None):
        arr = self._span(0, self._n)
        return arr if dtype is None else arr.astype(dtype, copy=False)

    def __eq__(self, other) -> bool:
        try:
            if len(other) != self._n:
                return False
        except TypeError:
            return NotImplemented
        try:
            mine, theirs = np.asarray(self), np.asarray(other)
            if object not in (mine.dtype, theirs.dtype):
                return bool(np.array_equal(mine, theirs))
        except ValueError:  # ragged tokens
            pass
        return list(self) == list(other)  # Python objects, one by one

    def __repr__(self) -> str:
        return (
            f"DeliveredTokens({self._n} tokens in "
            f"{len(self._ends)} chunks)"
        )


class StreamSession:
    """One client stream.  ``submit`` / ``close`` are called from the client
    thread; everything else is driven by the engine thread."""

    def __init__(
        self,
        sid: int,
        server,
        ingress: Sequence[str],
        egress: Sequence[str],
        admission_depth: int,
        array_ingress: Collection[str] = (),
    ):
        self.sid = sid
        self._server = server
        self.ingress = list(ingress)
        self.egress = list(egress)
        # Cross-thread channel: the client thread owns the writer endpoint
        # (submit), the engine thread owns the reader (pump) — so this MUST
        # use the deferred snapshot/publish protocol.  deferred=False's
        # _sync_now republishes *both* counters and is only safe when one
        # thread owns both endpoints.  A port in ``array_ingress`` (its
        # source feeds a device partition) queues numpy blocks; the others
        # queue Python objects for host actors.
        self.queues: Dict[str, object] = {
            name: (ArrayFifo if name in array_ingress else RingFifo)(
                admission_depth, name=f"s{sid}:{name}", deferred=True
            )
            for name in ingress
        }
        self.results: Dict[str, DeliveredTokens] = {
            name: DeliveredTokens() for name in egress
        }
        self.closed = False
        self.finished = threading.Event()
        self.pipeline: Optional[SessionPipeline] = None  # set by the server
        self.submitted_tokens = 0
        self.error: Optional[str] = None  # set by the engine on a dead stream
        # SLO timestamps (perf_counter_ns): TTFO = first delivery − first
        # submit; inter-block latency = gap between consecutive deliveries.
        # Written by the client thread (first_submit) and the engine thread
        # (deliveries) — single writer each, so no lock.
        self.first_submit_ns: Optional[int] = None
        self.first_delivery_ns: Optional[int] = None
        self.last_delivery_ns: Optional[int] = None

    # -- client side ---------------------------------------------------------
    def submit(
        self,
        values: Sequence,
        port: Optional[str] = None,
        *,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> None:
        """Enqueue one input submission, with admission backpressure.

        ``port`` may be omitted for single-ingress programs.  A submission
        larger than the admission chunk (``server.admission_chunk``, default
        the queue capacity) is *split at admission*: chunks enter the queue
        one at a time under backpressure, so one huge submission trickles in
        while the engine keeps serving every other stream — it can no
        longer park a whole stream's tokens ahead of everyone else's.

        When the queue lacks space: ``block=True`` waits (engine drains
        it), ``block=False`` raises ``AdmissionFull`` unless the *entire*
        submission fits right now — the client's cue to slow down.

        A port whose source feeds a device partition copies the submission
        once, here on the client thread, into a numpy block of the dtype
        numpy infers (Python floats float64, ints int64); staging casts it
        to the port's dtype.
        """
        if self.closed:
            raise ServeError(f"session {self.sid}: submit after close()")
        if port is None:
            if len(self.queues) != 1:
                raise ServeError(
                    f"session {self.sid}: program has ingress ports "
                    f"{sorted(self.queues)}; pass port="
                )
            port = next(iter(self.queues))
        try:
            q = self.queues[port]
        except KeyError:
            raise ServeError(
                f"session {self.sid}: unknown ingress {port!r} "
                f"(have {sorted(self.queues)})"
            ) from None
        values = (
            _token_block(values) if isinstance(q, ArrayFifo)
            else list(values)
        )
        # TTFO stamps BEFORE any admission wait: the SLO clock starts when
        # the client handed us tokens, so queueing delay under backpressure
        # is part of what the histogram measures, not silently excluded
        if self.first_submit_ns is None:
            self.first_submit_ns = time.perf_counter_ns()
        deadline = None if timeout is None else time.perf_counter() + timeout
        q.snapshot_writer()  # see the engine's latest published reads
        if not block and q.space() < len(values):
            raise AdmissionFull(
                f"session {self.sid}: admission queue {port!r} full "
                f"({q.capacity} tokens)"
            )
        step = min(
            q.capacity,
            getattr(self._server, "admission_chunk", None) or q.capacity,
        )
        for i in range(0, max(len(values), 1), step):
            chunk = values[i:i + step]
            while q.space() < len(chunk):
                if not self._server.wait_for_space(deadline):
                    # the deadline and the engine freeing space can race:
                    # re-check before failing a submit that would now fit
                    q.snapshot_writer()
                    if q.space() >= len(chunk):
                        break
                    raise AdmissionFull(
                        f"session {self.sid}: submit timed out after "
                        f"{timeout}s waiting for admission space on "
                        f"{port!r}"
                    )
                q.snapshot_writer()
            q.write(chunk)
            q.publish_writer()  # make the chunk visible to the engine thread
            self.submitted_tokens += len(chunk)
            split = 1 if len(values) > step and i == 0 else 0
            rec = getattr(self._server, "recorder", None)
            if rec is not None:
                rec.instant(
                    f"session:{self.sid}", "submit", "session",
                    {
                        "chunks": 1, "tokens": len(chunk),
                        "queued": q.count(), "split": split,
                    },
                )
            self._server.notify_work(
                chunks=1, tokens=len(chunk), split=split,
            )

    def close(self) -> None:
        """Mark end-of-stream; the session finishes once fully drained."""
        self.closed = True
        self._server.notify_work()

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait until every submitted token has been processed & delivered."""
        return self.finished.wait(timeout)

    # -- engine side ---------------------------------------------------------
    def queued_tokens(self, port: str) -> int:
        """Fresh reader-side count of one admission queue (engine thread
        only — snapshots the writer's latest publish)."""
        q = self.queues[port]
        q.snapshot_reader()
        return q.count()

    def output(self, port: Optional[str] = None) -> DeliveredTokens:
        """Tokens delivered on one egress port (the only one by default)."""
        if self.error is not None:
            raise ServeError(self.error)
        if port is None:
            if len(self.results) != 1:
                # multi-sink programs: prefer the collecting sink if unique
                raise ServeError(
                    f"session {self.sid}: program has egress ports "
                    f"{sorted(self.results)}; pass port="
                )
            port = next(iter(self.results))
        return self.results[port]


# ---------------------------------------------------------------------------
# Device stage — the PLink split open around the shared batcher
# ---------------------------------------------------------------------------


class DeviceStage:
    """Per-session stage/retire halves of one device partition's dispatch.

    Owns the session's state for one device partition and the FIFOs
    crossing that partition's boundary.  ``stage()`` drains boundary FIFOs
    into one ``(block,)`` staged payload — quantized to whole region
    iterations per destination actor (the plan precomputed on the
    ``DeviceProgram``) so a multi-rate op (e.g. the 8-point IDCT) never
    sees a torn block, and lockstep ports of one actor stay lane-aligned;
    the partition's batcher stacks payloads from many sessions into one
    launch and routes each lane's outputs back through ``retire()``.
    """

    def __init__(self, program, module: IRModule):
        self.program = program
        self.partition = getattr(program, "partition", "") or program.name
        self.state = program.fresh_state()  # donated by a sequential launch
        self.in_eps: Dict[str, ReaderEndpoint] = {}
        self.out_eps: Dict[str, WriterEndpoint] = {}
        # boundary ports grouped by destination actor; per-port granule =
        # lcm(port rate, region iteration quantum) — shared with PLink via
        # the program's staging plan
        self.groups: Dict[str, List[str]] = dict(program.in_groups)
        self.quantum: Dict[str, int] = dict(program.in_quanta)
        self.dtypes: Dict[str, object] = {
            f"{a}.{p}": _np_dtype(dt) for (a, p, dt) in program.in_ports
        }
        self.inflight = 0  # rounds this stage is riding right now
        self.tokens_staged = 0
        self.blocks_staged = 0  # chunks the last stage() filled
        self.tokens_retired = 0
        # megastep: payloads are (k, block) chunk stacks when the program
        # runs k>1 repetition-vector iterations per launch
        self.k = max(1, getattr(program, "megastep_k", 1))
        shape = (self.k, program.block) if self.k > 1 else (program.block,)
        # preallocated staging buffers, reused across launches — safe
        # because the batcher copies them (``pack_lanes`` stacks, the
        # sequential path ``jnp.asarray``s) inside the same ``launch`` call
        # that staged them, before any other stage() can repack
        self._bufs: Dict[str, Tuple[np.ndarray, np.ndarray]] = {
            key: (np.zeros(shape, dt), np.zeros(shape, bool))
            for key, dt in self.dtypes.items()
        }

    @property
    def pending(self) -> bool:
        """Riding at least one in-flight round (legacy name)."""
        return self.inflight > 0

    def _plan(self, closed=()) -> Dict[str, int]:
        """Tokens stageable per boundary port right now (whole granules,
        lane-aligned across each actor's ports, capped at one block),
        leaving out the port groups in ``closed``."""
        block = self.program.block
        plan: Dict[str, int] = {}
        for group, keys in self.groups.items():
            if group in closed:
                continue
            g = min(
                min(self.in_eps[k].count(), block) // self.quantum[k]
                for k in keys
            )
            if g > 0:
                for k in keys:
                    plan[k] = g * self.quantum[k]
        return plan

    def ready_tokens(self) -> int:
        """Tokens a ``stage()`` call would drain right now."""
        return sum(self._plan().values())

    def stage(self) -> Optional[Dict[str, Tuple[np.ndarray, np.ndarray]]]:
        """Drain up to ``k`` blocks per port into the reused staging
        buffers; None when nothing to do.  Riding an in-flight round does
        NOT block staging the next one — the continuous batcher chains
        rounds through the device-state future, so a session streams
        back-to-back launches without a drain barrier.  ``blocks_staged``
        counts the chunks that took tokens.  When the program's state is
        carried by stream ops, a port group that stages a short chunk
        stages nothing more this launch, so each port's valid tokens stay a
        prefix of the launch."""
        plan = self._plan()
        if not plan:
            return None
        block = self.program.block
        total = blocks = 0
        closed = set()
        for j in range(self.k):
            if j > 0:
                plan = self._plan(closed)
            for key in self.quantum:  # every in-port appears in the payload
                arr, mask = self._bufs[key]
                row_a = arr[j] if self.k > 1 else arr
                row_m = mask[j] if self.k > 1 else mask
                n = plan.get(key, 0)
                if n:
                    i = 0
                    for part in self.in_eps[key].read_blocks(n):
                        _stage_into(row_a[i:i + len(part)], part)
                        i += len(part)
                # zero the tail: reused buffers must never leak a previous
                # launch's tokens into masked-off padding
                row_a[n:] = 0
                row_m[:n] = True
                row_m[n:] = False
                total += n
            blocks += bool(plan)
            if self.program.state_slots:
                closed.update(
                    group for group, keys in self.groups.items()
                    if any(plan.get(k, 0) < block for k in keys)
                )
            if not plan and j + 1 < self.k:
                for arr, mask in self._bufs.values():
                    arr[j + 1:] = 0
                    mask[j + 1:] = False
                break
        staged = {key: self._bufs[key] for key in self.quantum}
        self.tokens_staged += total
        self.blocks_staged = blocks
        return staged

    def retire(self, outs) -> int:
        """Write one lane's outputs back to the host FIFOs (PLink §III-D).

        State is NOT written back here: the batcher rebinds ``self.state``
        to the launch's output-state future at dispatch time, which is what
        lets the next round launch before this one retires."""
        moved = 0
        for key, (vals, mask) in outs.items():
            vals = np.asarray(vals)
            keep = vals[np.asarray(mask)]
            if keep.size:
                # a RingFifo boxes host tokens; an ArrayFifo (to another
                # partition or a sink) queues the array itself
                self.out_eps[key].write(keep)
                moved += int(keep.size)
        self.inflight -= 1
        self.tokens_retired += moved
        return moved

    def idle(self) -> bool:
        return not self.inflight and not self._plan()


# ---------------------------------------------------------------------------
# Session pipeline
# ---------------------------------------------------------------------------


class SessionPipeline:
    """Executable serve-mode plumbing for one session over a lowered module.

    Built against the *current* program; a hot-swap rebuilds it (at a fully
    drained boundary) and transplants actor state by name.
    """

    def __init__(
        self,
        module: IRModule,
        session: StreamSession,
        device_programs,  # {partition id: DeviceProgram} (or one, or None)
        *,
        controller: str = "am",
        default_depth: int = 4096,
        max_execs_per_invoke: int = 10_000,
        carry_state: Optional[Dict[str, Dict]] = None,
        carry_fifos: Optional[Dict[Tuple, List]] = None,
        recorder=None,
        chaos=None,
    ):
        self.module = module
        self.session = session
        self.max_execs_per_invoke = max_execs_per_invoke
        self.recorder = recorder  # streamtrace (None = untraced server)
        self.chaos = chaos  # fault injection (None = no chaos)
        self._track = f"session:{session.sid}"

        hw_of = module.hw_assignment()
        devset = set(hw_of)
        if device_programs is None:
            device_programs = {}
        elif not isinstance(device_programs, dict):  # legacy single program
            device_programs = {
                getattr(device_programs, "partition", "")
                or device_programs.name: device_programs
            }
        sources = {
            n for n, a in module.actors.items()
            if not a.inputs and n not in devset
        }
        sinks = {
            n for n, a in module.actors.items()
            if not a.outputs and n not in devset
        }
        host = [
            n for n in module.topo_order()
            if n not in devset | sources | sinks
        ]

        # one DeviceStage per device partition — each rides its own
        # batcher lane, so two partitions pipeline inside one session too
        self.stages: Dict[str, DeviceStage] = {
            pid: DeviceStage(device_programs[pid], module)
            for pid in sorted({hw_of[a] for a in devset})
        }
        array_src = device_fed_ingress(module)
        self.fifos: Dict[Tuple, RingFifo] = {}     # channel key -> fifo
        self.ingress: Dict[str, RingFifo] = {}     # source name -> fifo
        self.egress: List[Tuple[str, RingFifo]] = []  # (sink name, fifo)
        readers: Dict[str, Dict[str, ReaderEndpoint]] = {a: {} for a in host}
        writers: Dict[str, Dict[str, WriterEndpoint]] = {a: {} for a in host}

        for ch in module.channels:
            s_pid, d_pid = hw_of.get(ch.src), hw_of.get(ch.dst)
            if s_pid is not None and s_pid == d_pid:
                continue  # compiled inside one device program
            if (
                s_pid is not None and (d_pid is not None or ch.dst in sinks)
            ) or ch.src in array_src:
                # device -> device across partitions, device -> the
                # session's result buffer, or the client -> a device
                # partition: numpy blocks, never per-token Python objects
                f = ArrayFifo(
                    ch.resolved_depth or default_depth,
                    name=f"s{session.sid}:{ch}",
                )
            else:
                f = RingFifo(
                    ch.resolved_depth or default_depth,
                    name=f"s{session.sid}:{ch}",
                    deferred=False,  # one engine thread drives the pipeline
                )
            self.fifos[ch.key] = f
            # writer side
            if ch.src in sources:
                if ch.src in self.ingress:
                    raise ServeError(
                        f"{module.name}: source {ch.src!r} fans out at the "
                        f"graph level; serve mode supports one channel per "
                        f"ingress port"
                    )
                self.ingress[ch.src] = f
            elif s_pid is not None:
                self.stages[s_pid].out_eps[f"{ch.src}.{ch.src_port}"] = (
                    WriterEndpoint(f)
                )
            else:
                writers[ch.src][ch.src_port] = WriterEndpoint(f)
            # reader side
            if ch.dst in sinks:
                self.egress.append((ch.dst, f))
            elif d_pid is not None:
                self.stages[d_pid].in_eps[f"{ch.dst}.{ch.dst_port}"] = (
                    ReaderEndpoint(f)
                )
            else:
                readers[ch.dst][ch.dst_port] = ReaderEndpoint(f)
            # fault-path transplant: a forced swap (partition quarantine) or
            # a checkpoint restore rebuilds the pipeline *with* residual
            # tokens still sitting in host-visible FIFOs.  Residue is keyed
            # by AUTHORED channel key because fusion renames lowered keys
            # differently across placements (``fusedN``/``member__PORT``).
            if carry_fifos:
                residue = carry_fifos.get(
                    authored_channel_key(module, ch.key)
                )
                if residue is not None and len(residue):
                    _write_tokens(f, residue)
                    f.publish_writer()

        # per-channel totals already folded into server telemetry — the
        # engine records *deltas* periodically, so long-lived sessions feed
        # the online repartitioner too, not just finished ones; transplanted
        # residue starts past the mark (it was already recorded once by the
        # pipeline that originally moved it)
        self._link_marks: Dict[Tuple, int] = {
            key: f.total_written
            for key, f in self.fifos.items()
            if f.total_written
        }

        carry = carry_state or {}
        self.instances: Dict[str, object] = {}
        for name in host:
            impl = module.actors[name].impl
            env = PortEnv(readers[name], writers[name])
            inst = (
                ActorMachine(impl, env)
                if controller == "am"
                else BasicController(impl, env)
            )
            if name in carry:  # hot-swap: persistent actor state survives
                inst.state = carry[name]
            self.instances[name] = inst
        # fused host regions: members collapse into one block executor per
        # group (the member machines stay wrapped inside for tail fallback
        # and state transplant) — the same executor the thread scheduler
        # fires, so serve-mode host rounds get the identical fast path
        self.host_fused: Dict[str, object] = {}
        if module.meta.get("host_fused"):
            from repro.runtime.host_fused import attach_host_fused

            self.host_fused = attach_host_fused(
                module, self.instances, readers, writers, self.fifos
            )
        if carry:
            for stage in self.stages.values():
                stage.state = _transplant_device_state(
                    stage.program, stage.state, carry
                )

        # one admission pump moves at most this many tokens per round — a
        # whole number of source firings keeps multi-token actions intact
        self.pump_quantum = {
            name: math.lcm(
                *(max(r, 1) for _, r in module.actors[name].rate.produces),
                1,
            )
            for name in self.ingress
        }

    # -- engine-side round pieces -------------------------------------------
    def pump(self, telemetry=None) -> Tuple[int, int]:
        """Admission queues -> ingress FIFOs (bounded by FIFO space).
        Returns the tokens moved and how many of them moved as numpy blocks
        (an ``ArrayFifo`` queue into an ``ArrayFifo`` ingress: no per-token
        work).  A hot swap, repartition or degrade that moved the ingress's
        consumer leaves the queue and the FIFO of different kinds; the
        tokens convert here, at that one boundary.

        Engine-thread only; it owns the queues' reader endpoints, so each
        pump snapshots the client's published writes and publishes its own
        reads back (the deferred cross-thread FIFO protocol)."""
        moved = blocks = 0
        for name, fifo in self.ingress.items():
            q = self.session.queues[name]
            quantum = self.pump_quantum[name]
            n = min(self.session.queued_tokens(name), fifo.space())
            n -= n % quantum
            if n <= 0:
                continue
            for part in q.read_blocks(n):
                _write_tokens(fifo, part)
            q.publish_reader()  # free the space for blocked submitters
            as_blocks = isinstance(q, ArrayFifo) and isinstance(fifo, ArrayFifo)
            nb = n if as_blocks else 0
            moved += n
            blocks += nb
            if telemetry is not None:
                telemetry.pumped(n, q.count(), nb)
        return moved, blocks

    def host_round(self, telemetry=None) -> int:
        """Fire every host actor machine once (round-robin, like a thread
        partition's fire step).  Fused host regions ride the same list as
        single block-wise instances; their telemetry key carries the member
        list so profile ingestion can split the time back over authored
        actors (``core.profiler.profile_from_telemetry``)."""
        execs = 0
        rec = self.recorder
        ch = self.chaos
        for name, inst in self.instances.items():
            if ch is not None:
                # chaos site: one occurrence per actor invoke per round —
                # ``actor:<name>@s<sid>`` targets one session's actors
                ch.poke(f"actor:{name}@s{self.session.sid}")
            key = getattr(inst, "telemetry_key", name)
            with span(rec, self._track, "actor", key) as sp:
                e = inst.invoke(self.max_execs_per_invoke)
                if e:
                    sp.args["fires"] = e
                else:
                    sp.discard()  # only productive invokes are recorded
            if e and telemetry is not None:
                # the span's own duration: a trace replay reproduces the
                # live actor-time totals exactly
                telemetry.actor_fired(key, e, sp.dur_ns)
            execs += e
        return execs

    def drain_egress(self) -> Tuple[int, int]:
        """Egress FIFOs -> session result buffers.  Returns the tokens
        delivered and how many of them arrived as array blocks (from a
        device-fed sink's ``ArrayFifo``, appended without boxing)."""
        moved = blocks = 0
        for sink, fifo in self.egress:
            n = fifo.count()
            if not n:
                continue
            buf = self.session.results[sink]
            if isinstance(fifo, ArrayFifo):
                for blk in fifo.read_blocks(n):
                    buf.extend(blk)
                blocks += n
            else:
                buf.extend(fifo.read(n))
            moved += n
        return moved, blocks

    @property
    def stage(self) -> Optional[DeviceStage]:
        """The single device stage (legacy accessor); None when host-only,
        first lane when several."""
        if not self.stages:
            return None
        return next(iter(self.stages.values()))

    def occupancy(self) -> int:
        """Tokens anywhere inside the pipeline (excludes admission queues)."""
        toks = sum(f.occupancy() for f in self.fifos.values())
        for stage in self.stages.values():
            toks += stage.inflight  # in-flight rounds count as occupancy
        return toks

    def quiescent(self) -> bool:
        return self.occupancy() == 0

    def take_link_deltas(self) -> Dict[Tuple, int]:
        """Per-channel tokens moved since the last call (marks advance)."""
        out: Dict[Tuple, int] = {}
        for key, f in self.fifos.items():
            d = f.total_written - self._link_marks.get(key, 0)
            if d:
                out[key] = d
                self._link_marks[key] = f.total_written
        return out

    def carry_state(self) -> Dict[str, Dict]:
        """Actor state to transplant into a rebuilt pipeline (hot-swap)."""
        carry: Dict[str, Dict] = {}
        for n, inst in self.instances.items():
            machines = getattr(inst, "machines", None)
            if machines is not None:  # fused host region: per-member states
                carry.update({m: mach.state for m, mach in machines.items()})
            else:
                carry[n] = inst.state
        for stage in self.stages.values():
            carry.update(_flatten_device_state(stage))
        return carry

    def carry_fifos(self) -> Dict[Tuple, Sequence]:
        """Residual tokens per **authored** channel key (non-consuming).

        The fault-path complement of ``carry_state``: a forced swap cannot
        wait for quiescence (the device that would drain the tokens is the
        thing that failed), so whatever is still sitting in host-visible
        FIFOs is peeked here and written into the rebuilt pipeline's FIFOs
        (`carry_fifos=` on the constructor).  Device-internal channels hold
        no cross-launch tokens (SDF regions launch whole iterations), so
        host FIFOs + admission queues are the complete token residue.
        A client's tokens in an ``ArrayFifo`` ingress stay one numpy array,
        which a rebuilt ``RingFifo`` ingress takes as Python scalars."""
        ingress = set(self.ingress.values())
        out: Dict[Tuple, Sequence] = {}
        for key, f in self.fifos.items():
            n = f.count()
            if n:
                toks = f.peek(n)
                out[authored_channel_key(self.module, key)] = (
                    toks if f in ingress else list(toks)
                )
        return out


# -- device-state transplant across placements ------------------------------


def _host_value(leaf):
    """A device state leaf as the host holds it: a Python scalar for a
    0-d leaf (what a host actor's state holds, so a degraded actor goes on
    computing as the host does), else a numpy array."""
    arr = np.asarray(jax.device_get(leaf))
    return arr.item() if arr.ndim == 0 else arr


def _flatten_device_state(stage: DeviceStage) -> Dict[str, Dict]:
    """Per-member view of the device state, undoing fusion grouping (a
    stateful stream region's registers go back to their members' state
    keys), with host values as leaves."""
    flat: Dict[str, Dict] = {}
    fused = stage.program.fused or {}
    slots = stage.program.state_slots
    for actor, st in jax.tree.map(_host_value, stage.state).items():
        members = fused.get(actor)
        if actor in slots:
            flat.update({m: {} for m in members})
            for (m, k), v in zip(slots[actor], st["regs"]):
                flat[m][k] = v.item()
        elif members and set(st) == set(members):
            flat.update({m: dict(s) for m, s in st.items()})
        else:
            flat[actor] = st
    return flat


def _transplant_device_state(program, init, carry: Dict[str, Dict]):
    """Rebuild a device-state tree from carried per-member state where the
    actor names (and state keys) still match; everything else reinitializes.
    Carried leaves take the dtype of the leaves they replace."""

    def like(value, leaf):
        return jnp.asarray(np.asarray(value, dtype=np.asarray(leaf).dtype))

    def take(st: Dict, old: Dict) -> Dict:
        if set(old) != set(st):
            return st
        return {k: like(old[k], st[k]) for k in st}

    fused = program.fused or {}
    state = {}
    for actor, st in init.items():
        members = fused.get(actor)
        if actor in program.state_slots:
            regs = np.array(st["regs"])
            for j, (m, k) in enumerate(program.state_slots[actor]):
                if k in carry.get(m, {}):
                    regs[j] = carry[m][k]
            state[actor] = {"regs": like(regs, st["regs"])}
        elif members and set(st) == set(members):
            state[actor] = {
                m: take(st[m], carry.get(m, st[m])) for m in st
            }
        else:
            state[actor] = take(st, carry.get(actor, st))
    return state
