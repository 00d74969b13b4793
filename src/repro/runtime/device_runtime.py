"""Device partition code generation (the paper's hardware backend, §III-B).

A device partition is the hw region of a *lowered IR module*
(``repro.ir.lower``) compiled into ONE jitted XLA program — the TPU analogue
of synthesizing the partition's actors to RTL inside a dynamic region.  By
the time this backend runs, the middle-end has already legalized the
placement, resolved FIFO depths, and (by default) fused every static-rate
(SDF) sub-region into a single fused actor — so the step traced here invokes
one ``vector_fire`` per *region*, not one per authored actor, and the fused
regions dispatch to the Pallas stream kernel (``repro.kernels.stream_fused``)
on TPU with a bit-identical jnp path on CPU.

Execution model: the partition step processes a *block* of tokens per
invocation (vectorized firing — the analogue of the HLS controller taking the
maximum number of steps per invocation).  Dynamic-rate actors (e.g. Filter)
emit a validity mask; tokens flow between in-partition actors as
(values, mask) pairs so the whole dynamic dataflow stays inside one fused
program.  The step also returns an ``idle`` flag — hardware idleness
detection (§III-B): the host (PLink) never polls internal state, it just
reads the flag.

Megasteps: ``megastep`` runs ``megastep_k`` blocks ("chunks") per launch so
the host↔device boundary cost — stage, dispatch, sync, retire — is paid once
per k repetition-vector iterations instead of once per iteration.  Inputs
arrive as ``(k, block)`` stacks; on the generic path a ``lax.scan`` threads
the chunks through ``raw_step`` sequentially (bit-identical to k separate
launches by construction), and when every member is a fused Pallas stream
region the whole stack runs as ONE flat multi-iteration grid launch over
``k*block`` tokens (``flat_megastep`` — the stream kernel's token axis is
shape-polymorphic and its block transforms never straddle a chunk edge).
Actor state never round-trips to host between launches: the jitted entry
points donate the state argument, and PLink chains each launch off the
previous launch's state *future*.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.actor import Actor
from repro.core.graph import ActorGraph, GraphError
from repro.ir.ir import IRModule


@dataclass
class DeviceProgram:
    """Compiled device partition."""

    name: str
    actors: List[str]
    in_ports: List[Tuple[str, str, str]]  # (actor, port, dtype)
    out_ports: List[Tuple[str, str, str]]
    step: Callable  # jitted: (state, {in:(vals,mask)}) -> (state, {out:(vals,mask)}, idle)
    init_state: Dict[str, Any]
    block: int
    fused: Dict[str, Tuple[str, ...]] = None  # fused actor -> member names
    # the untraced step — what batched_step vmaps over (``step`` is jitted
    # with donation, which a vmap must not close over)
    raw_step: Callable = None
    # staging plan: boundary in-ports grouped by destination actor, and the
    # token granule each port must be staged in (lcm of the port's rate and
    # the destination's whole-region iteration quantum).  Stagers (PLink and
    # the serve-mode DeviceStage) drain whole granules, lane-aligned across
    # each actor's ports — a lockstep port pair (e.g. a MAC's XIN/AIN) can
    # never skew, and a multi-rate member never sees a torn block.
    in_groups: Dict[str, List[str]] = field(default_factory=dict)
    in_quanta: Dict[str, int] = field(default_factory=dict)
    # which XCF partition this program implements, its declared processing
    # element, and the concrete JAX device it is bound to (None = default
    # placement — single-device hosts and legacy callers)
    partition: str = ""
    pe: str = ""
    device: Any = None
    # megastep: chunks (repetition-vector blocks) per launch.  k == 1 means
    # the classic one-block step; k > 1 means ``megastep``/``raw_megastep``
    # accept ``(k, block)`` input stacks and return ``(k, block)`` outputs.
    megastep_k: int = 1
    # True when the megastep lowers to ONE flat (k*block,)-token launch
    # (every member a fused Pallas stream region) instead of a k-chunk scan
    flat_megastep: bool = False
    # whether the jitted entry points donate the state argument (state stays
    # device-resident across launches; callers must never reuse a donated
    # state tree)
    donate: bool = True
    # the untraced megastep — what batched_megastep vmaps over
    raw_megastep: Callable = None
    # per fused actor whose state is carried by masked stream ops, as its
    # registers' vector ``{"regs": (n,)}``: the (member, state key) each
    # register holds, so a transplant maps them to and from the members'
    # own state by name.  Those ops take each register from the last valid
    # token, so with any, the stagers fill a port's valid tokens as a
    # prefix of the launch (a port group that stages a short chunk stages
    # nothing more in that launch)
    state_slots: Dict[str, Tuple[Tuple[str, str], ...]] = field(
        default_factory=dict)
    # jitted megastep: (state, {in: (vals (k,block), mask (k,block))}) ->
    # (state', {out: (k,block)...}, idle); donates state like ``step``
    megastep: Callable = None
    _batched: Dict[str, Callable] = field(default_factory=dict, repr=False)

    def launch(self, state, inputs):
        """Dispatch one launch: the megastep when this program has one
        (``megastep_k > 1`` — inputs are ``(k, block)`` stacks), else the
        classic one-block ``step``.  Both donate ``state``."""
        if self.megastep_k > 1:
            return self.megastep(state, inputs)
        return self.step(state, inputs)

    def fresh_state(self) -> Dict[str, Any]:
        """A new device copy of ``init_state`` for one launch chain.

        The jitted entry points donate their state argument, and on an
        accelerator donation deletes the buffer — so every chain (a PLink
        per run, a ``DeviceStage`` per serve session) starts from its own
        copy, and ``init_state`` itself is never handed to a launch."""
        return jax.tree.map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x,
            self.init_state,
        )

    def batched_step(self, batch: int) -> Callable:
        """One jitted launch stepping ``batch`` independent session lanes.

        Signature mirrors ``step`` with a leading batch axis everywhere:
        ``(state (B,...), {in: (vals (B,block), mask (B,block))}) ->
        (state', {out: (B,block)...}, idle (B,))``.  Lanes are vmapped, so
        lane *i* is bit-identical to an unbatched ``step`` over lane *i*'s
        state and block — B sessions cost one XLA dispatch (and, inside a
        fused region, one Pallas launch) instead of B.

        One traced-through-vmap callable backs every batch size; jit
        specializes (and caches) per concrete B, so callers memoize the
        widths they launch (the continuous batcher pads a round up to an
        already-compiled width within ``LANE_SLACK``) to bound recompiles.
        """
        assert self.raw_step is not None, (
            f"{self.name}: legacy DeviceProgram without raw_step cannot batch"
        )
        if "vmap" not in self._batched:
            self._batched["vmap"] = jax.jit(
                jax.vmap(self.raw_step, in_axes=(0, 0))
            )
        return self._batched["vmap"]

    def batched_megastep(self, batch: int) -> Callable:
        """``batched_step`` for megastep programs: one jitted launch running
        ``batch`` lanes of ``(k, block)`` chunk stacks — lane *i* bit-
        identical to an unbatched ``megastep`` over lane *i*."""
        assert self.raw_megastep is not None, (
            f"{self.name}: program compiled without a megastep"
        )
        if "vmap_mega" not in self._batched:
            self._batched["vmap_mega"] = jax.jit(
                jax.vmap(self.raw_megastep, in_axes=(0, 0))
            )
        return self._batched["vmap_mega"]

    def batched_init_state(self, batch: int) -> Dict[str, Any]:
        """``init_state`` broadcast to ``batch`` lanes."""
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x), (batch,) + jnp.shape(jnp.asarray(x))
            ),
            self.init_state,
        )

    @staticmethod
    def pack_lanes(
        payloads: Sequence[Dict[str, Tuple[Any, Any]]],
    ) -> Dict[str, Tuple[Any, Any]]:
        """Per-lane staged payloads -> one batched input dict.

        Each payload maps ``"actor.port" -> (vals, mask)`` host arrays of
        shape ``(block,)`` (or ``(k, block)`` for megastep programs); the
        result stacks them along a new leading lane axis, matching the
        leading batch axis of ``batched_step``/``batched_megastep``.  Lane
        order is kept — lane *i* of the launch is ``payloads[i]``."""
        keys = payloads[0].keys()
        return {
            k: (
                jnp.asarray(np.stack([p[k][0] for p in payloads])),
                jnp.asarray(np.stack([p[k][1] for p in payloads])),
            )
            for k in keys
        }

    @staticmethod
    def stack_states(states: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
        """Per-session state trees -> one batched tree (lane order kept).
        A tree with leaves stacks in one jitted call per width (compiled
        when the width is first launched), not one eager op per leaf."""
        if not jax.tree.leaves(states[0]):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        return _stack_states(list(states))

    @staticmethod
    def unstack_states(batched: Dict[str, Any], width: int) -> List[Dict]:
        """Every lane's state tree of a batched tree, in one jitted call
        per width when the tree has leaves."""
        if not jax.tree.leaves(batched):
            return [batched] * width
        return _unstack_states(batched, width)


@jax.jit
def _stack_states(states):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


@functools.partial(jax.jit, static_argnums=1)
def _unstack_states(batched, width):
    return [jax.tree.map(lambda x: x[i], batched) for i in range(width)]


def region_quantum(module: IRModule, actor_name: str) -> int:
    """Token granularity one boundary port of ``actor_name`` must be staged
    in so no member op ever sees a torn block.

    A fused region's boundary port inherits its member's per-firing rate
    (often 1), but members *inside* the region may fire at coarser rates —
    the 8-point IDCT consumes 8 tokens per firing behind a rate-1 descale.
    Staging a block that is not a whole number of region iterations would
    hand such a member a block mixing valid tokens with padding.  The
    analyzer's region-restricted repetition vector gives the iteration
    shape: member ``m`` fires ``q[m]`` times, moving ``rate * q[m]`` tokens
    per port — the lcm of those per-iteration throughputs is the granule.
    """
    import math

    from repro.analysis.rates import member_rates, region_repetition

    ir = module.actors[actor_name]
    members = list(ir.fused_from or (actor_name,))
    q = region_repetition(module, members)
    rate_of, _edges = member_rates(module, members)
    counts: List[int] = []
    for m in members:
        r = rate_of(m)
        for _p, n in tuple(r.consumes) + tuple(r.produces):
            if n > 0:
                counts.append(n * q.get(m, 1))
    return math.lcm(*counts) if counts else 1


def staging_plan(
    module: IRModule,
    in_ports: Sequence[Tuple[str, str, str]],
    members: Optional[Sequence[str]] = None,
) -> Tuple[Dict[str, List[str]], Dict[str, int]]:
    """Group boundary in-ports and compute each port's staging granule —
    the shared plan behind PLink and the serve-mode DeviceStage.

    Ports are grouped by the *internal connected component* of the
    partition their destination belongs to, and a stager drains whole
    granules lane-aligned across a group.  Destination-actor grouping alone
    is not enough: two boundary streams that converge downstream *inside*
    the partition (e.g. a bitonic stage fed partly by a host deal lane and
    partly by another device partition's lane) must advance the same number
    of iterations per launch, or the internal wires pair tokens from
    different stream positions — internal wires are not buffered across
    launches.  Disjoint internal components keep independent progress, so a
    placement like {descale, clip} with the idct on the host between them
    still pipelines instead of deadlocking on the empty downstream group.

    Granules come from the analyzer's repetition vector, solved once per
    internal component over the *authored* members (fused actors expand to
    their ``fused_from``): port ``a.p`` stages ``consume_rate(p) *
    q[member]`` tokens per component iteration — the replacement for the
    old lcm-of-all-rates derivation, agreeing with it on every Table-I
    network but tighter on mixed-rate chains.
    """
    from repro.analysis.rates import port_member, region_repetition
    from repro.ir.ir import connected_components

    sub = set(members) if members is not None else {a for (a, _p, _d) in in_ports}
    comp = connected_components(sub, module.channels)
    comp_members: Dict[str, List[str]] = {}
    for a in sub:
        ir = module.actors[a]
        comp_members.setdefault(comp[a], []).extend(ir.fused_from or (a,))
    comp_q = {
        k: region_repetition(module, ms) for k, ms in comp_members.items()
    }

    groups: Dict[str, List[str]] = {}
    quanta: Dict[str, int] = {}
    for (a, p, _dt) in in_ports:
        key = f"{a}.{p}"
        groups.setdefault(comp[a], []).append(key)
        c = max(module.actors[a].rate.consume_rate(p), 1)
        q = comp_q[comp[a]].get(port_member(module, a, p), 1)
        quanta[key] = c * q
    return groups, quanta


def resolve_pe_device(pe: str):
    """Map an XCF ``PartitionSpec.pe`` string to a concrete JAX device.

    ``"cpu"``/``"gpu"``/``"tpu"`` (optionally ``":<index>"``) select the
    i-th device of that platform — with ``xla_force_host_platform_device_count``
    (or a real multi-chip host) different partitions land on different
    devices and genuinely overlap.  Such a PE names one device: when this
    host has no device of that platform, or fewer than ``index + 1``, it
    raises ``GraphError`` listing the devices present — a partition never
    lands silently on another chip or platform.  Accelerator-model strings
    like ``"tpu-v5e-16x16"`` (the XCF default) bind to the default device;
    host PEs (``"x86_64"``) and anything unrecognized return None (default
    placement).
    """
    if not pe:
        return None
    import re

    m = re.fullmatch(r"(cpu|gpu|tpu)(?::(\d+))?", pe.lower())
    if m is not None:
        devices = jax.devices()
        same = [d for d in devices if d.platform == m.group(1)]
        index = int(m.group(2) or 0)
        if index >= len(same):
            raise GraphError(
                f"PE {pe!r} names {m.group(1)} device {index}, but this "
                f"host has: {', '.join(str(d) for d in devices)}"
            )
        return same[index]
    if pe.lower().startswith(("tpu", "gpu", "accel")):
        return jax.devices()[0]
    return None


def default_vector_fire(actor: Actor):
    """Vectorize a 1-action SDF actor's scalar fire over a token block via scan."""
    action = actor.actions[0]
    in_ports = [p.name for p in actor.inputs]
    out_ports = [p.name for p in actor.outputs]

    def vf(state, ins):  # ins: {port: (vals (N,), mask (N,))}
        n = next(iter(ins.values()))[0].shape[0] if ins else None
        assert n is not None, "sourceless actors need an explicit vector_fire"

        def body(st, tok):
            vals = {p: [tok[p][0]] for p in in_ports}
            st, outs = action.fire(st, vals)
            ovals = {p: outs[p][0] for p in out_ports}
            return st, ovals

        toks = {p: (ins[p][0], ins[p][1]) for p in in_ports}
        state, outs = jax.lax.scan(
            body, state, {p: toks[p] for p in in_ports}
        )
        mask = ins[in_ports[0]][1]
        return state, {p: (outs[p], mask) for p in out_ports}

    return vf


# legacy name, kept for external callers
_default_vector_fire = default_vector_fire


def _lower_legacy(graph: ActorGraph, names: Sequence[str]) -> IRModule:
    """Lower a raw graph with ``names`` on the device partition, *without*
    fusion — the legacy ``compile_partition(graph, [...])`` contract exposes
    per-actor boundary ports, which fusion would rename."""
    from repro.core.xcf import make_xcf
    from repro.ir.passes import lower

    sub = set(names)
    assignment = {
        a: ("accel" if a in sub else "t0") for a in graph.actors
    }
    return lower(graph, make_xcf(graph.name, assignment), fuse=False)


def resolve_megastep_k(
    module: IRModule,
    sub,
    init_state: Dict[str, Any],
    in_ports,
    block: int,
    megastep,
) -> int:
    """Clamp the requested megastep target to what one partition supports.

    A launch of k chunks stages up to ``k*block`` tokens per boundary port
    and may retire as many, and PLink keeps a second launch in flight while
    the first computes — every crossing FIFO must absorb ``2*k*block``
    tokens, so k is floored to ``depth // (2*block)`` over the partition's
    boundary channels (depth inference sizes them for the requested k; an
    XCF-pinned shallower depth clamps here, flagged by the SB206 lint).
    A partition holding state that masked stream ops do not carry is
    clamped to 1: the block scan that vectorizes such an actor advances its
    state over *padding* positions too.  State carried by a fused stream
    region's ``delay`` ops (its ``state_slots``) takes each register
    from the last valid token under the mask, so those regions, like
    stateless partitions, keep megastep ≡ per-iteration bitwise on ragged
    tails.  Partitions with no boundary inputs (on-device sources) have no
    staged work to amortize and also stay at 1.
    """
    from repro.ir.passes import resolve_megastep

    if megastep is None:
        megastep = module.meta.get("megastep", 1)
    k = resolve_megastep(megastep)
    if k <= 1:
        return 1
    if not in_ports:
        return 1
    if any(s and not getattr(module.actors[a].impl, "state_slots", ())
           for a, s in init_state.items()):
        return 1
    for ch in module.channels:
        if (ch.src in sub) == (ch.dst in sub):
            continue
        depth = ch.resolved_depth
        if depth:
            k = min(k, max(1, depth // (2 * block)))
    return max(1, k)


def compile_partition(
    src,
    actor_names: Optional[Sequence[str]] = None,
    *,
    block: int = 1024,
    name: str = "accel",
    mesh=None,
    donate: bool = True,
    partition: Optional[str] = None,
    device: Any = None,
    megastep=None,
) -> DeviceProgram:
    """Compile one hw region of ``src`` into one jitted step.

    ``src`` is a lowered ``IRModule`` (the supported path — fusion and depth
    inference already applied) or a raw ``ActorGraph`` plus ``actor_names``
    (legacy path: lowered on the spot, unfused, per-actor boundary ports).
    ``partition`` selects a region by id when the module has several hw
    regions (``compile_hw_partitions`` builds them all); ``device``
    overrides the JAX device binding otherwise resolved from the region's
    ``pe`` string.  ``megastep`` overrides the lowered module's
    ``meta["megastep"]`` chunks-per-launch target; either way the effective
    ``megastep_k`` is clamped per partition (``resolve_megastep_k``).
    """
    pe = ""
    if isinstance(src, IRModule):
        module = src
        if partition is not None:
            region = module.regions.get(partition)
            if region is None or region.kind != "hw":
                raise GraphError(
                    f"{module.name}: no hw partition {partition!r} (hw "
                    f"partitions: {[r.id for r in module.hw_regions()]})"
                )
            actor_names = region.actors
            name = region.id
            pe = region.pe
        elif actor_names is None:
            hws = module.hw_regions()
            assert hws, f"{module.name}: module has no hw region"
            assert len(hws) == 1, (
                f"{module.name}: {len(hws)} hw regions "
                f"({[r.id for r in hws]}); pass partition= (or use "
                f"compile_hw_partitions) to pick one"
            )
            actor_names = hws[0].actors
            name = hws[0].id
            pe = hws[0].pe
        names = sorted(actor_names)
    else:
        assert actor_names is not None, "compile_partition(graph, names)"
        names = list(actor_names)
        for a in names:
            actor = src.actors[a]
            assert actor.device_ok, (
                f"{a}: {actor.host_only_reason or 'host-only actor'}"
            )
        module = _lower_legacy(src, names)
        names = sorted(names)
    sub = set(names)

    # boundary ports (post-fusion names — what PLink stages against)
    in_ports, out_ports = [], []
    internal: List = []
    for ch in module.channels:
        if ch.dst in sub and ch.src not in sub:
            in_ports.append((ch.dst, ch.dst_port, ch.dtype))
        elif ch.src in sub and ch.dst not in sub:
            out_ports.append((ch.src, ch.src_port, ch.dtype))
        elif ch.src in sub and ch.dst in sub:
            internal.append(ch)

    # topological order of the partition's actors (feedback not supported on device)
    order = [a for a in module.topo_order() if a in sub]

    impls = {a: module.actors[a].impl for a in names}
    vfs = {
        a: (impls[a].vector_fire or default_vector_fire(impls[a]))
        for a in names
    }
    init_state = {a: dict(impls[a].initial_state) for a in names}
    actor_in_ports = {a: [p.name for p in impls[a].inputs] for a in names}

    def step(state, inputs):
        """inputs: {(actor,port): (vals (block,), mask (block,))}"""
        wires: Dict[Tuple[str, str], Tuple[jax.Array, jax.Array]] = {}
        for (a, p, _dt) in in_ports:
            wires[(a, p)] = inputs[f"{a}.{p}"]
        new_state = dict(state)
        outs: Dict[str, Tuple[jax.Array, jax.Array]] = {}
        produced = jnp.zeros((), jnp.int32)
        for a in order:
            ins = {p: wires[(a, p)] for p in actor_in_ports[a]}
            st, a_outs = vfs[a](new_state[a], ins)
            new_state[a] = st
            for ch in internal:
                if ch.src == a:
                    wires[(ch.dst, ch.dst_port)] = a_outs[ch.src_port]
            for (sa, sp, _dt) in out_ports:
                if sa == a:
                    outs[f"{sa}.{sp}"] = a_outs[sp]
        for v, m in outs.values():
            produced = produced + jnp.sum(m.astype(jnp.int32))
        consumed = sum(
            jnp.sum(m.astype(jnp.int32)) for _, m in inputs.values()
        ) if inputs else jnp.zeros((), jnp.int32)
        idle = (produced + consumed) == 0
        return new_state, outs, idle

    if device is None:
        device = resolve_pe_device(pe)
    if device is not None:
        # Commit the state to the partition's device: jit then compiles (and
        # keeps, via donation) the whole step there, and staged inputs follow
        # the committed state's placement.  This is the sub-mesh binding from
        # ``PartitionSpec.pe`` — on a single-device host every partition
        # resolves to that device and the binding is a no-op.
        init_state = jax.device_put(init_state, device)
    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    in_groups, in_quanta = staging_plan(module, in_ports, names)
    too_small = {k: q for k, q in in_quanta.items() if q > block}
    if too_small:
        raise GraphError(
            f"{name}: block={block} is smaller than the staging quantum of "
            f"{too_small} — a whole region iteration must fit in one staged "
            f"block; raise block= to at least the largest quantum"
        )

    megastep_k = resolve_megastep_k(
        module, sub, init_state, in_ports, block, megastep
    )
    flat = False
    raw_megastep = jitted_megastep = None
    if megastep_k > 1:
        # Flat path: when every member is a fused Pallas stream region the
        # step body is shape-polymorphic over the token axis (fused_stream
        # flattens a (k, block) stack into one k*block-token grid launch),
        # so the megastep is literally ONE kernel launch with a k×-larger
        # grid — provided no block transform (matmul8 8-blocks, perm
        # P-blocks) straddles a chunk edge, i.e. block % transform_unit == 0.
        from repro.kernels.stream_fused.ops import transform_unit

        def _flat_ok(a: str) -> bool:
            prog_obj = getattr(impls[a], "stream_program", None)
            return (
                module.actors[a].codegen == "pallas"
                and prog_obj is not None
                and block % transform_unit(prog_obj) == 0
            )

        flat = all(_flat_ok(a) for a in names)

        if flat:
            raw_megastep = step  # shape-polymorphic: (k, block) in, one launch
        else:
            def raw_megastep(state, inputs):
                """Scan ``raw_step`` over the k chunks — bit-identical to k
                sequential launches (same state threading, same per-chunk
                masks), with the boundary paid once."""
                def body(st, chunk):
                    st, outs, idle = step(st, chunk)
                    return st, (outs, idle)

                state, (outs, idles) = jax.lax.scan(body, state, inputs)
                return state, outs, jnp.all(idles)

        jitted_megastep = jax.jit(
            raw_megastep, donate_argnums=(0,) if donate else ()
        )
    return DeviceProgram(
        name=name,
        actors=names,
        in_ports=in_ports,
        out_ports=out_ports,
        in_groups=in_groups,
        in_quanta=in_quanta,
        step=jitted,
        raw_step=step,
        init_state=init_state,
        block=block,
        fused={
            a: module.actors[a].fused_from
            for a in names
            if module.actors[a].is_fused
        },
        partition=partition or name,
        pe=pe,
        device=device,
        megastep_k=megastep_k,
        flat_megastep=flat,
        donate=donate,
        raw_megastep=raw_megastep,
        megastep=jitted_megastep,
        state_slots={a: impls[a].state_slots for a in names
                     if getattr(impls[a], "state_slots", ())},
    )


def compile_hw_partitions(
    module: IRModule,
    *,
    block: int = 1024,
    donate: bool = True,
    megastep=None,
) -> Dict[str, "DeviceProgram"]:
    """Compile every hw region of a lowered module — one independently
    jitted ``DeviceProgram`` per device partition, each bound to the JAX
    device its ``PartitionSpec.pe`` resolves to.  Returns ``{partition id:
    program}`` in stable order.  ``megastep`` defaults to the module's
    lowered ``meta["megastep"]`` target."""
    return {
        r.id: compile_partition(
            module, block=block, donate=donate, partition=r.id,
            megastep=megastep,
        )
        for r in module.hw_regions()
        if r.actors  # an empty hw partition has nothing to compile
    }
