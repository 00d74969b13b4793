"""Public surface of the fused-stream kernel: the op-program representation,
the backend dispatcher, and the (opt-in) algebraic folder.

A ``StreamProgram`` is the fusion pass's codegen target: a register file of
``(N,)`` token wires, a static op list, and the registers holding each fused
output port.  The device step traces ``fused_stream`` once per region; on TPU
it lowers to the Pallas kernel, on CPU to the jnp reference (which XLA fuses
into one loop) — both compute the identical op sequence.

A program may carry state: ``delay`` ops read and write ``n_state`` registers
(one float32 each), which the call takes and returns beside the wires.  The
call's tokens are one stream in order (every leading axis flattened), and a
delay's new register is the last *valid* token of its wire, counted by the
mask of the program input named in the op: the stagers fill each input's
valid tokens as a prefix of the call, so padding never enters a register.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.stream_fused.ref import fused_stream_ref

OP_KINDS = (
    "affine", "clip", "matmul8", "axpy", "const", "min2", "max2", "perm",
    "delay",
)


@dataclass(frozen=True)
class StreamOp:
    kind: str                 # one of OP_KINDS
    ins: Tuple[int, ...]      # value registers read
    out: int                  # value register written
    params: Tuple = ()        # static floats / arrays (matmul8 basis, perm
    #                           idx); a delay's (state register, mask input)

    def __str__(self) -> str:
        ps = ", ".join(
            f"A{list(p.shape)}" if hasattr(p, "shape") else f"{p:g}"
            for p in self.params
        )
        return f"r{self.out} = {self.kind}({ps})({', '.join(f'r{i}' for i in self.ins)})"


@dataclass(frozen=True)
class StreamProgram:
    n_inputs: int
    n_regs: int
    ops: Tuple[StreamOp, ...]
    outputs: Tuple[int, ...]  # registers of the fused output ports, in order
    n_state: int = 0          # state registers the delay ops carry

    def __str__(self) -> str:
        body = "; ".join(str(op) for op in self.ops) or "passthrough"
        outs = ", ".join(f"r{i}" for i in self.outputs)
        state = f", {self.n_state} state" if self.n_state else ""
        return (f"stream({self.n_inputs} in, {self.n_regs} regs{state}): "
                f"{body} -> {outs}")


def transform_unit(program: StreamProgram) -> int:
    """Block size the program's block transforms need whole: the lcm of
    ``matmul8``'s 8 and each ``perm``'s P (1 when it has none).  The device
    runtime gates the *flat* megastep on it: a ``(k, block)`` chunk stack
    may flatten into one ``k*block``-token launch only when ``block`` is a
    multiple of it, which keeps every chunk's block transforms whole and
    therefore bit-identical to k separate launches."""
    units = [1]
    for op in program.ops:
        if op.kind == "matmul8":
            units.append(8)
        elif op.kind == "perm":
            units.append(len(op.params[0]))
    return math.lcm(*units)


# TPU vector lanes: the Pallas kernel lays tokens out in rows of a multiple
# of this, and applies block transforms as block-diagonal matmuls per row
LANES = 128
# widest row the kernel accepts — a perm whose size shares few factors with
# 128 would otherwise need a huge block-diagonal matrix in VMEM
MAX_ROW_WIDTH = 512


def row_width(program: StreamProgram) -> int:
    """Tokens per lane-dense row of the Pallas kernel: a multiple of the 128
    lanes holding whole transform blocks."""
    return math.lcm(LANES, transform_unit(program))


def pallas_tileable(program: StreamProgram) -> bool:
    """Whether the Pallas kernel can lay ``program`` out lane-dense (the
    fusion pass picks the jnp codegen for regions it cannot)."""
    return row_width(program) <= MAX_ROW_WIDTH


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def fused_stream(
    inputs: Sequence[jax.Array],  # per-port (N,) or (B, N) float32 arrays
    program: StreamProgram,
    *,
    use: str = "auto",  # "auto" | "pallas" | "ref"
    state=None,   # (n_state,) float32 registers of a stateful program
    counts=None,  # (n_inputs,) int32 valid tokens per input this call
):
    """Run one fused region over a token block.

    ``auto`` picks the jnp reference on CPU (it compiles into the enclosing
    device-step jit) and the Pallas kernel elsewhere; ``pallas`` forces the
    kernel (interpret mode on CPU — used by the equivalence tests).

    Inputs with a leading batch axis — ``(B, N)``, one row per server
    session — run as ONE kernel launch (the Pallas path flattens the token
    axis; the ref path is shape-polymorphic), with each row bit-identical to
    a per-session dispatch (see ``ref.fused_stream_ref``).  A stateful
    program instead reads all of a call's tokens as one stream (the serve
    batcher vmaps it per session) and returns ``(outputs, new_state)``.
    """
    if use == "ref" or (use == "auto" and _on_cpu()):
        return fused_stream_ref(inputs, program, state, counts)
    from repro.kernels.stream_fused.kernel import fused_stream_fwd

    stack = jnp.stack([x.astype(jnp.float32) for x in inputs])
    out = fused_stream_fwd(stack, program, state=state, counts=counts,
                           interpret=_on_cpu())
    if program.n_state:
        out, new_state = out
        return [out[j] for j in range(len(program.outputs))], new_state
    return [out[j] for j in range(len(program.outputs))]


# ---------------------------------------------------------------------------
# Algebraic folding (opt_level=2) — NOT bit-preserving, therefore opt-in.
# ---------------------------------------------------------------------------


def _use_counts(program: StreamProgram) -> List[int]:
    uses = [0] * program.n_regs
    for op in program.ops:
        for i in op.ins:
            uses[i] += 1
    for i in program.outputs:
        uses[i] += 1
    return uses


def fold(program: StreamProgram) -> StreamProgram:
    """Collapse affine∘affine chains and same-x axpy ladders.

    ``affine(p2,m2,q2)∘affine(p1,m1,q1)`` becomes one affine; a ladder of
    ``a += c_i * x`` over the same ``x`` becomes ``a += (Σ c_i) * x``.  The
    result is algebraically equal but rounds differently in float32 — the
    pipeline only applies it at ``opt_level=2``, and the golden tests compare
    it with ``allclose`` rather than bitwise.
    """
    ops = list(program.ops)
    changed = True
    while changed:
        changed = False
        uses = _use_counts(
            StreamProgram(program.n_inputs, program.n_regs, tuple(ops),
                          program.outputs)
        )
        produced = {op.out: k for k, op in enumerate(ops)}
        for k, op in enumerate(ops):
            if op.kind == "affine" and op.ins[0] in produced:
                j = produced[op.ins[0]]
                prev = ops[j]
                if (
                    prev.kind == "affine"
                    and uses[prev.out] == 1
                    and prev.out not in program.outputs
                ):
                    p1, m1, q1 = prev.params
                    p2, m2, q2 = op.params
                    # ((x+p1)*m1+q1 + p2)*m2 + q2
                    ops[k] = StreamOp(
                        "affine", prev.ins, op.out,
                        (p1, m1 * m2, (q1 + p2) * m2 + q2),
                    )
                    del ops[j]
                    changed = True
                    break
            if op.kind == "axpy" and op.ins[1] in produced:
                j = produced[op.ins[1]]
                prev = ops[j]
                if (
                    prev.kind == "axpy"
                    and prev.ins[0] == op.ins[0]  # same x wire
                    and uses[prev.out] == 1
                    and prev.out not in program.outputs
                ):
                    (c1,) = prev.params
                    (c2,) = op.params
                    ops[k] = StreamOp(
                        "axpy", (op.ins[0], prev.ins[1]), op.out, (c1 + c2,)
                    )
                    del ops[j]
                    changed = True
                    break
            if op.kind == "axpy" and op.ins[1] in produced:
                j = produced[op.ins[1]]
                prev = ops[j]
                if (
                    prev.kind == "const"
                    and prev.params == (0.0,)
                    and uses[prev.out] == 1
                    and prev.out not in program.outputs
                ):
                    (c,) = op.params
                    # a = 0 + c*x  ->  affine mul
                    ops[k] = StreamOp(
                        "affine", (op.ins[0],), op.out, (0.0, c, 0.0)
                    )
                    del ops[j]
                    changed = True
                    break
    return StreamProgram(
        program.n_inputs, program.n_regs, tuple(ops), program.outputs,
        program.n_state,
    )
