"""Pure-jnp oracle for fused SDF stream regions.

Evaluates a ``StreamProgram`` (see ``ops.py``) over a register file of
``(N,)`` token arrays.  Each op mirrors — bit-for-bit in float32 — the
expression the corresponding *unfused* actor's ``vector_fire`` computes, so
the fused region is verifiably equivalent to the per-actor device path:

  affine   (x + pre) * mul + post      identity components skipped exactly
  clip     jnp.clip(x, lo, hi)
  matmul8  x.reshape(-1, 8) @ B        the 8-point block transform (HIGHEST)
  axpy     a + c * x                   one MAC tap
  const    jnp.full_like               rate seed (e.g. FIR acc = 0)
  min2/max2  jnp.minimum / jnp.maximum compare-exchange lanes
  perm     x.reshape(-1, P)[:, idx]    block reorder (e.g. JPEG zigzag descan)
  delay    [s, x_0 .. x_{N-2}]         one-token delay through state register
                                       s; s := x_{count-1} (the last valid)

This module is also the device fallback: on CPU the fused region runs this
reference inside the device-step ``jax.jit`` (XLA fuses the op chain), while
on TPU ``ops.fused_stream`` dispatches to the Pallas kernel.

``fused_stream_np`` is the *host* twin: the same op list evaluated with pure
numpy in float64 — the arithmetic the per-token Python interpreter performs
(Python floats are IEEE doubles) — so a fused host region is bit-identical to
its interpreted members by construction.  ``matmul8`` is the one op whose
interpreted analogue computes in float32 (the actor casts its 8-block before
the matmul); the numpy evaluator performs the identical float32 round trip.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def apply_op(kind: str, params, ins: Sequence[jax.Array]) -> jax.Array:
    if kind == "affine":
        pre, mul, post = params
        x = ins[0]
        if pre != 0.0:
            x = x + pre
        if mul != 1.0:
            x = x * mul
        if post != 0.0:
            x = x + post
        return x
    if kind == "clip":
        lo, hi = params
        return jnp.clip(ins[0], lo, hi)
    if kind == "matmul8":
        (basis,) = params
        x = ins[0]
        # reshape back to the input's own shape so the op is polymorphic over
        # a leading batch axis ((B, N) wires — the multi-session server); for
        # 1-D wires this is exactly the original reshape(-1).  HIGHEST keeps
        # the float32 product exact on backends whose default precision
        # rounds matmul inputs to bfloat16.
        return jnp.matmul(
            x.reshape(-1, 8), jnp.asarray(basis),
            precision=jax.lax.Precision.HIGHEST,
        ).reshape(x.shape)
    if kind == "axpy":
        (c,) = params
        x, a = ins
        return a + c * x
    if kind == "const":
        (v,) = params
        return jnp.full_like(ins[0], v)
    if kind == "min2":
        return jnp.minimum(ins[0], ins[1])
    if kind == "max2":
        return jnp.maximum(ins[0], ins[1])
    if kind == "perm":
        (idx,) = params
        x = ins[0]
        # like matmul8: P-blocks never straddle a row when N % P == 0, so
        # the op is polymorphic over a leading batch axis
        blocks = x.reshape(-1, len(idx))
        return blocks[:, jnp.asarray(idx)].reshape(x.shape)
    raise ValueError(f"unknown stream op {kind!r}")


def delay_ref(x: jax.Array, s: jax.Array, count: jax.Array):
    """The ``delay`` op over one call: the call's tokens flattened in order
    are shifted by one, register ``s`` entering first; the new register is
    the last valid token (the ``count``-th), or ``s`` when none is valid.
    Pure data movement, so any evaluation of it is bitwise the same."""
    flat = x.reshape(-1)
    shifted = jnp.concatenate([s.reshape(1).astype(flat.dtype), flat[:-1]])
    last = flat[jnp.maximum(count - 1, 0)]
    return shifted.reshape(x.shape), jnp.where(count > 0, last, s)


def fused_stream_ref(
    inputs: Sequence[jax.Array], program, state=None, counts=None,
):
    """Evaluate ``program`` over per-port input arrays; returns output arrays
    in the program's declared output order (and the new ``(n_state,)``
    registers after them when the program carries state).

    Inputs may be ``(N,)`` wires or ``(B, N)`` batched wires (one row per
    server session, or one row per megastep *chunk* — the ``(k, block)``
    stacks the flat megastep feeds through): every op is elementwise over the
    token axis except ``matmul8``, whose 8-blocks never straddle a row when
    ``N % 8 == 0``, so each row of the batched result is bit-identical to the
    row run alone.
    """
    regs: List[jax.Array] = [None] * program.n_regs
    for i, x in enumerate(inputs):
        regs[i] = x
    new_state = [None] * program.n_state
    for op in program.ops:
        if op.kind == "delay":
            slot, mask_input = op.params
            regs[op.out], new_state[slot] = delay_ref(
                regs[op.ins[0]], state[slot], counts[mask_input]
            )
            continue
        regs[op.out] = apply_op(op.kind, op.params, [regs[i] for i in op.ins])
    outs = [regs[i] for i in program.outputs]
    if program.n_state:
        return outs, jnp.stack(new_state)
    return outs


# ---------------------------------------------------------------------------
# Host (numpy / float64) evaluator — the fused-host-region backend
# ---------------------------------------------------------------------------


def apply_op_np(kind: str, params, ins: Sequence[np.ndarray]) -> np.ndarray:
    """One stream op over numpy wires, mirroring — bit-for-bit — the
    arithmetic the member's *scalar* fire function performs on the same
    tokens.  Wires keep the stream's own dtype: Python-float tokens
    evaluate in float64 (Python floats are IEEE doubles), device-fed
    ``np.float32`` tokens in float32 — exactly the NEP-50 promotion the
    scalar path's ``np.float32 scalar ⊕ python float`` expressions follow.

    Unlike ``apply_op``, the affine identity components are NOT skipped: the
    interpreted path always evaluates the full ``(v + pre) * mul + post``
    expression, and skipping ``+ 0.0`` would preserve a ``-0.0`` the scalar
    path normalizes.
    """
    if kind == "affine":
        pre, mul, post = params
        return (ins[0] + pre) * mul + post
    if kind == "clip":
        lo, hi = params
        return np.clip(ins[0], lo, hi)
    if kind == "matmul8":
        (basis,) = params
        x = ins[0]
        # the interpreted actor casts each 8-block to float32, matmuls, and
        # re-boxes as Python floats — the identical float32 round trip
        y = x.astype(np.float32).reshape(-1, 8) @ np.asarray(basis, np.float32)
        return y.astype(np.float64).reshape(x.shape)
    if kind == "axpy":
        (c,) = params
        x, a = ins
        return a + c * x
    if kind == "const":
        (v,) = params
        return np.full_like(ins[0], v)
    if kind == "min2":
        return np.minimum(ins[0], ins[1])
    if kind == "max2":
        return np.maximum(ins[0], ins[1])
    if kind == "perm":
        (idx,) = params
        x = ins[0]
        return x.reshape(-1, len(idx))[:, np.asarray(idx)].reshape(x.shape)
    raise ValueError(f"unknown stream op {kind!r}")


def fused_stream_np(
    inputs: Sequence[np.ndarray], program
) -> List[np.ndarray]:
    """Evaluate ``program`` over numpy wires on the host — the block
    executor behind fused static-rate *software* regions (see
    ``repro.runtime.host_fused``).  Wires keep each input stream's inferred
    dtype (Python floats -> float64, device-retired tokens -> float32), so
    promotion mirrors the scalar interpreter's.  No masks: host regions are
    static-rate by construction, so every staged token is valid."""
    regs: List[np.ndarray] = [None] * program.n_regs
    for i, x in enumerate(inputs):
        arr = np.asarray(x)
        if arr.dtype.kind not in "fiu":  # mixed/object tokens: box as double
            arr = arr.astype(np.float64)
        regs[i] = arr
    for op in program.ops:
        regs[op.out] = apply_op_np(
            op.kind, op.params, [regs[i] for i in op.ins]
        )
    return [regs[i] for i in program.outputs]
