"""SDF region fusion: collapse a static-rate subgraph of a device partition
into one fused actor with a single ``vector_fire``.

Two codegen strategies, picked per region:

  * **stream** ("pallas") — every member carries a declarative ``stream_op``
    spec (``("affine", pre, mul, post)``, ``("mac", c)``, ``("cmpx", asc)``,
    ``("matmul8", basis)``, ...).  The region compiles to a
    ``StreamProgram`` — a static op list over token-wire registers —
    dispatched through ``repro.kernels.stream_fused`` (Pallas kernel on TPU,
    jnp reference on CPU).  Op expressions mirror the member
    ``vector_fire``s bit-for-bit in float32, so fusion is equivalence-tested
    exactly against the unfused path.
  * **composed** ("jnp") — fallback when specs are missing: member
    ``vector_fire``s are evaluated in topological order inside one traced
    function.  Still one device actor (one wire map, one state tree) instead
    of N.

Masks never change inside an SDF region (rates are static, guards absent),
so each fused output's validity mask is *selected* from the fused inputs at
build time — the runtime moves only values.

Stateful members fuse too when their spec names the state op that carries
their state (``("fir_tap", c)``: a delay register).  The program then holds
the members' registers, one ``delay`` op each; the fused actor's state is
one float32 vector of them (``{"regs": (n_state,)}``, one device buffer
per session lane), and the actor's ``state_slots`` name each register's
``(member, state key)``, so hot swap and checkpoints map the registers
back to their taps by name.  A delay's new
register is the last valid token under its input's mask, which the
stagers fill as a prefix of each launch; so such a region is built only
where every delay's mask comes from a staged boundary input (one fed from
outside the partition, not by a dynamic-rate actor beside it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.actor import Action, Actor, Port
from repro.core.graph import GraphError
from repro.kernels.stream_fused import StreamOp, StreamProgram, fold, fused_stream
from repro.kernels.stream_fused.ops import pallas_tileable


@dataclass
class FusedBuild:
    """Everything the pass needs to splice a fused actor into the module."""

    actor: Actor                       # synthetic impl (vector_fire only)
    codegen: str                       # "pallas" | "jnp"
    in_port_of: Dict[Tuple[str, str], str]   # (member, port) -> fused port
    out_port_of: Dict[Tuple[str, str], str]
    members: Tuple[str, ...]
    program: Optional[StreamProgram] = None


def _fused_port(actor: str, port: str) -> str:
    return f"{actor}__{port}"


def _region_io(module, members: Sequence[str]):
    """Boundary input/output endpoints and internal channels of the region."""
    sub = set(members)
    ins, outs, internal = [], [], []
    for ch in module.channels:
        if ch.dst in sub and ch.src not in sub:
            ins.append(ch)
        elif ch.src in sub and ch.dst not in sub:
            outs.append(ch)
        elif ch.src in sub and ch.dst in sub:
            internal.append(ch)
    return ins, outs, internal


# ---------------------------------------------------------------------------
# Stream-program codegen (the Pallas path)
# ---------------------------------------------------------------------------


# spec kinds whose member state a stream op carries: the state keys each
# kind's ops read and write, in slot order
STATE_KEYS = {"fir_tap": ("z",)}


def _translate_spec(spec, in_reg, new_reg, emit, new_slot):
    """Lower one actor's ``stream_op`` spec to ops.

    Returns ``{out_port: (value_reg, mask_reg)}`` or None when the spec kind
    is unknown (the whole region then falls back to composed codegen).
    ``in_reg(port) -> (reg, mask_reg)``; masks are propagated exactly the way
    the member's ``vector_fire`` propagates them.  ``new_slot(key, mask)
    -> (slot, mask input)`` allocates a state register for the member's
    state ``key``, governed by the fused input ``mask``.
    """
    kind = spec[0]
    if kind == "affine":
        pre, mul, post = (float(x) for x in spec[1:])
        x, m = in_reg("IN")
        o = new_reg()
        emit(StreamOp("affine", (x,), o, (pre, mul, post)))
        return {"OUT": (o, m)}
    if kind == "clip":
        lo, hi = (float(x) for x in spec[1:])
        x, m = in_reg("IN")
        o = new_reg()
        emit(StreamOp("clip", (x,), o, (lo, hi)))
        return {"OUT": (o, m)}
    if kind == "matmul8":
        basis = np.asarray(spec[1], np.float32)
        x, m = in_reg("IN")
        o = new_reg()
        emit(StreamOp("matmul8", (x,), o, (basis,)))
        return {"OUT": (o, m)}
    if kind == "mac":
        c = float(spec[1])
        x, xm = in_reg("XIN")
        a, am = in_reg("AIN")
        o = new_reg()
        emit(StreamOp("axpy", (x, a), o, (c,)))
        return {"XOUT": (x, xm), "AOUT": (o, am)}
    if kind == "fir_tap":
        c = float(spec[1])
        x, xm = in_reg("XIN")
        a, am = in_reg("AIN")
        d, o = new_reg(), new_reg()
        emit(StreamOp("delay", (x,), d, new_slot("z", xm)))
        emit(StreamOp("axpy", (x, a), o, (c,)))
        return {"XOUT": (d, xm), "AOUT": (o, am)}
    if kind == "fir_seed":
        x, m = in_reg("IN")
        z = new_reg()
        emit(StreamOp("const", (x,), z, (0.0,)))
        return {"XOUT": (x, m), "AOUT": (z, m)}
    if kind == "cmpx":
        ascending = bool(spec[1])
        a, am = in_reg("IN0")
        b, bm = in_reg("IN1")
        lo, hi = new_reg(), new_reg()
        emit(StreamOp("min2", (a, b), lo))
        emit(StreamOp("max2", (a, b), hi))
        if ascending:
            return {"OUT0": (lo, am), "OUT1": (hi, bm)}
        return {"OUT0": (hi, am), "OUT1": (lo, bm)}
    if kind == "dup":
        x, m = in_reg("IN")
        n = int(spec[1])
        return {f"O{i}": (x, m) for i in range(n)}
    if kind == "perm":
        idx = np.asarray(spec[1], np.int32)
        x, m = in_reg("IN")
        o = new_reg()
        emit(StreamOp("perm", (x,), o, (idx,)))
        return {"OUT": (o, m)}
    return None


def _try_stream_program(
    module, order: Sequence[str], b_ins, b_outs, internal, *, opt_level: int,
):
    """Build a StreamProgram for the region (members in topological
    ``order``), or None if any member lacks a recognizable spec / has state
    no stream op carries / isn't float32.  Returns ``(program, output
    masks, state slots)``, a slot being ``(member, state key)``."""
    for m in order:
        impl = module.actors[m].impl
        if impl.stream_op is None:
            return None
        carried = STATE_KEYS.get(impl.stream_op[0], ())
        if set(impl.initial_state) != set(carried):
            return None
        if any(p.dtype != "float32" for p in impl.inputs + impl.outputs):
            return None

    n_regs = len(b_ins)
    ops: List[StreamOp] = []
    # (member, in_port) -> (value reg, mask source: fused input port name)
    wire: Dict[Tuple[str, str], Tuple[int, str]] = {}
    for i, ch in enumerate(b_ins):
        wire[(ch.dst, ch.dst_port)] = (i, _fused_port(ch.dst, ch.dst_port))

    def new_reg() -> int:
        nonlocal n_regs
        n_regs += 1
        return n_regs - 1

    input_of = {_fused_port(ch.dst, ch.dst_port): i
                for i, ch in enumerate(b_ins)}
    slots: List[Tuple[str, str]] = []

    for m in order:
        spec = module.actors[m].impl.stream_op

        def new_slot(key: str, mask: str, _m=m) -> Tuple[int, int]:
            slots.append((_m, key))
            return len(slots) - 1, input_of[mask]

        def in_reg(port: str, _m=m):
            try:
                return wire[(_m, port)]
            except KeyError:
                raise GraphError(
                    f"fusion: {_m}.{port} has no producer inside or outside "
                    f"the region"
                ) from None

        produced = _translate_spec(spec, in_reg, new_reg, ops.append,
                                   new_slot)
        if produced is None:
            return None
        for ch in internal:
            if ch.src == m:
                wire[(ch.dst, ch.dst_port)] = produced[ch.src_port]
        for ch in b_outs:
            if ch.src == m:
                wire[(m, "__out__" + ch.src_port)] = produced[ch.src_port]

    out_regs, out_masks = [], []
    for ch in b_outs:
        reg, mask = wire[(ch.src, "__out__" + ch.src_port)]
        out_regs.append(reg)
        out_masks.append(mask)
    prog = StreamProgram(len(b_ins), n_regs, tuple(ops), tuple(out_regs),
                         len(slots))
    if opt_level >= 2:
        prog = fold(prog)
    return prog, out_masks, slots


def _staged_masks(module, order, b_ins, built) -> bool:
    """Whether every delay's mask comes from a boundary input fed from
    outside the region's partition — the stager then fills its valid
    tokens as a prefix of the launch, which the delay op relies on.  A
    stateless program needs nothing."""
    program = built[0]
    hw_of = module.hw_assignment()
    home = hw_of.get(order[0])
    for op in program.ops:
        if op.kind == "delay":
            src = b_ins[op.params[1]].src
            if hw_of.get(src) == home:
                return False
    return True


def _stateful_vf(program, out_masks, in_names, out_names):
    """The fused actor's step for a program with state registers: the
    registers ride in the state as one vector, and each input's
    valid-token count comes from its mask."""

    def vf(state, ins):
        counts = jnp.stack([jnp.sum(ins[p][1].astype(jnp.int32))
                            for p in in_names])
        vals, regs = fused_stream([ins[p][0] for p in in_names], program,
                                  state=state["regs"], counts=counts)
        return {"regs": regs}, {
            o: (v, ins[m][1]) for o, v, m in zip(out_names, vals, out_masks)
        }

    return vf


# ---------------------------------------------------------------------------
# Host-region codegen (fused block execution of static-rate *software* regions)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HostFusedSpec:
    """Codegen product of ``fuse-sdf-host-regions`` (see ``passes.py``).

    Unlike device fusion, host fusion rewrites *nothing*: the members stay in
    the module, their channels keep their keys, and this spec just tells the
    runtimes how to drive the region as one block executor
    (``repro.runtime.host_fused.HostFusedRegion``) — bulk-reading the
    boundary channels listed here, evaluating ``program`` with the numpy
    float64 evaluator (``kernels.stream_fused.fused_stream_np``), and
    bulk-writing the outputs.  Keeping the members intact is what makes the
    per-token interpreted fallback (dynamic tails, blocked outputs) free.
    """

    members: Tuple[str, ...]                      # topological order
    program: StreamProgram
    in_keys: Tuple[Tuple[str, str, str, str], ...]   # program input order
    out_keys: Tuple[Tuple[str, str, str, str], ...]  # program output order
    internal_keys: Tuple[Tuple[str, str, str, str], ...]
    quantum: int            # tokens per whole region iteration (lcm of rates)
    fires_each: Tuple[int, ...]  # per-member firings per iteration (repetition
    #                              vector entries, aligned with ``members``)
    fires_per_quantum: int  # interpreted member firings one quantum replaces
    block: int              # max tokens per fused invocation

    def __repr__(self) -> str:  # keep ir_dump meta lines readable
        return (
            f"HostFusedSpec({'+'.join(self.members)}, q={self.quantum}, "
            f"{len(self.program.ops)} ops)"
        )


def build_host_fused(
    module, members: Sequence[str], *, opt_level: int = 1, block: int = 1024
) -> Optional[HostFusedSpec]:
    """Lower one static-rate software region to a ``HostFusedSpec``, or None
    when any member falls outside the stream-op palette (the region then
    stays fully interpreted)."""
    from repro.analysis.rates import region_repetition

    order = [a for a in module.topo_order() if a in set(members)]
    b_ins, b_outs, internal = _region_io(module, order)
    try:
        built = _try_stream_program(
            module, order, b_ins, b_outs, internal, opt_level=opt_level
        )
    except GraphError:  # e.g. a feedback edge inside the group
        return None
    if built is None or built[2]:  # host regions hold no state
        return None
    program, _masks, _slots = built
    # The analyzer's region-restricted repetition vector is the single
    # source of truth for iteration shape: member m fires q[m] times per
    # region iteration, and every boundary channel moves rate*q[endpoint]
    # tokens.  The block executor drives all boundary fifos with one scalar
    # quantum, so those per-channel counts must agree — true across the 1:1
    # stream-op palette; anything else stays interpreted.
    q = region_repetition(module, order)
    fires_each = [q[m] for m in order]
    counts = set()
    for ch in b_ins:
        counts.add(
            module.actors[ch.dst].rate.consume_rate(ch.dst_port) * q[ch.dst]
        )
    for ch in b_outs:
        counts.add(
            module.actors[ch.src].rate.produce_rate(ch.src_port) * q[ch.src]
        )
    if len(counts) != 1 or 0 in counts:
        return None
    quantum = counts.pop()
    fires = sum(fires_each)
    return HostFusedSpec(
        members=tuple(order),
        program=program,
        in_keys=tuple(ch.key for ch in b_ins),
        out_keys=tuple(ch.key for ch in b_outs),
        internal_keys=tuple(ch.key for ch in internal),
        quantum=quantum,
        fires_each=tuple(fires_each),
        fires_per_quantum=fires,
        block=max(block, quantum),
    )


# ---------------------------------------------------------------------------
# Composed-vector_fire codegen (the jnp fallback)
# ---------------------------------------------------------------------------


def _member_vf(impl: Actor) -> Callable:
    if impl.vector_fire is not None:
        return impl.vector_fire
    from repro.runtime.device_runtime import default_vector_fire

    return default_vector_fire(impl)


def _composed_vf(module, order, b_ins, b_outs, internal):
    """One function evaluating the whole region member-by-member — the exact
    computation the unfused device step performs, minus the per-actor
    partition plumbing.  Endpoint names are snapshotted eagerly: the fusion
    pass rewrites the boundary IRChannel objects to the fused actor's name
    right after this closure is built."""
    vfs = {m: _member_vf(module.actors[m].impl) for m in order}
    in_ports = {m: [p.name for p in module.actors[m].impl.inputs] for m in order}
    in_map = [
        ((ch.dst, ch.dst_port), _fused_port(ch.dst, ch.dst_port))
        for ch in b_ins
    ]
    out_map = [
        ((ch.src, ch.src_port), _fused_port(ch.src, ch.src_port))
        for ch in b_outs
    ]
    wiring = [(ch.src, ch.src_port, ch.dst, ch.dst_port) for ch in internal]

    def vf(state, ins):
        wires = {ep: ins[fp] for ep, fp in in_map}
        new_state = dict(state)
        outs = {}
        for m in order:
            m_ins = {p: wires[(m, p)] for p in in_ports[m]}
            st, m_outs = vfs[m](new_state[m], m_ins)
            new_state[m] = st
            for (s, sp, d, dp) in wiring:
                if s == m:
                    wires[(d, dp)] = m_outs[sp]
            for (s, sp), fp in out_map:
                if s == m:
                    outs[fp] = m_outs[sp]
        return new_state, outs

    return vf


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def build_fused(
    module, members: Sequence[str], name: str, *, opt_level: int = 1
) -> FusedBuild:
    """Synthesize the fused actor for an SDF region of ``module``."""
    order = [a for a in module.topo_order() if a in set(members)]
    b_ins, b_outs, internal = _region_io(module, order)

    in_ports = [
        Port(_fused_port(ch.dst, ch.dst_port),
             module.actors[ch.dst].port(ch.dst_port).dtype)
        for ch in b_ins
    ]
    out_ports = [
        Port(_fused_port(ch.src, ch.src_port),
             module.actors[ch.src].port(ch.src_port).dtype)
        for ch in b_outs
    ]
    in_names = [p.name for p in in_ports]
    out_names = [p.name for p in out_ports]

    built = _try_stream_program(
        module, order, b_ins, b_outs, internal, opt_level=opt_level
    )
    if built is not None and not _staged_masks(module, order, b_ins, built):
        built = None
    if built is not None and pallas_tileable(built[0]):
        program, out_masks, slots = built
        if slots:
            vf = _stateful_vf(program, tuple(out_masks), in_names, out_names)
            init_state = {"regs": np.array(
                [module.actors[m].impl.initial_state[k] for m, k in slots],
                np.float32)}
        else:
            def vf(state, ins, _prog=program, _masks=tuple(out_masks)):
                vals = fused_stream([ins[p][0] for p in in_names], _prog)
                return state, {
                    o: (v, ins[m][1])
                    for o, v, m in zip(out_names, vals, _masks)
                }

            init_state = {}
        codegen = "pallas"
    else:
        program = None
        vf = _composed_vf(module, order, b_ins, b_outs, internal)
        codegen = "jnp"
        init_state = {
            m: dict(module.actors[m].impl.initial_state) for m in order
        }

    # Boundary rates: each fused port keeps its member's per-firing rate.
    consumes = {
        _fused_port(ch.dst, ch.dst_port):
            module.actors[ch.dst].rate.consume_rate(ch.dst_port)
        for ch in b_ins
    }
    produces = {
        _fused_port(ch.src, ch.src_port):
            module.actors[ch.src].rate.produce_rate(ch.src_port)
        for ch in b_outs
    }

    def no_scalar_fire(st, t):  # pragma: no cover - fused regions are hw-only
        raise NotImplementedError(
            f"fused region {name} executes on the device partition only"
        )

    actor = Actor(
        name=name,
        inputs=in_ports,
        outputs=out_ports,
        actions=[
            Action("fused", consumes=consumes, produces=produces,
                   fire=no_scalar_fire)
        ],
        initial_state=init_state,
        device_ok=True,
        vector_fire=vf,
    )
    if codegen == "pallas":
        # expose the StreamProgram on the actor impl: the device runtime's
        # flat-megastep gate reads it to size (k, block) chunk stacks against
        # the program's transform_unit
        actor.stream_program = program
        actor.state_slots = tuple(slots)
    return FusedBuild(
        actor=actor,
        codegen=codegen,
        in_port_of={(ch.dst, ch.dst_port): _fused_port(ch.dst, ch.dst_port)
                    for ch in b_ins},
        out_port_of={(ch.src, ch.src_port): _fused_port(ch.src, ch.src_port)
                     for ch in b_outs},
        members=tuple(order),
        program=program,
    )
