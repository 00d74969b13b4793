"""Fused SDF stream-region kernel (Pallas TPU).

One fused region = one ``pl.pallas_call``: the whole chain of per-actor
elementwise/block ops runs over a token tile while it sits in VMEM — one HBM
read of the input wire stack and one write of the output stack, instead of a
round trip per actor.  The op list is static at trace time (it comes from the
fusion pass), so the kernel body unrolls into straight-line VPU/MXU code.

Layout: the token axis is lane-dense.  A ``(n_in, ..., N)`` float32 wire
stack is laid out as ``(n_in, rows, W)`` with ``W = row_width(program)`` — a
multiple of the 128 TPU lanes and of every block transform's size — and the
grid tiles the row axis in ``(rows_per_tile, W)`` tiles that satisfy the TPU
(8, 128) block rule.  Because a row holds whole transform blocks, ``matmul8``
and ``perm`` become ONE ``(tile, W) @ (W, W)`` block-diagonal matmul per tile
(the 8x8 basis or the P-point one-hot repeated along the diagonal) instead of
a per-block reshape, which the TPU compiler cannot lower.  Matmuls run at
``precision=HIGHEST`` so float32 tokens are not rounded through bfloat16 and
a one-hot ``perm`` stays exact.  Every op but ``matmul8`` is bitwise equal
to the jnp reference; ``matmul8`` sums the same eight nonzero products of
each output among zero terms.  On a v5e that matched XLA's 8-wide matmul
bit for bit; in interpret mode on the CPU it agrees to float32 rounding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import GraphError
from repro.kernels.stream_fused.ops import LANES, row_width, transform_unit
from repro.kernels.stream_fused.ref import apply_op

_SUBLANES = 8
# VMEM budget for one grid step: double-buffered input/output tiles plus the
# live register file, kept well under the 16 MiB default scoped VMEM of v5e
_TILE_BUDGET_BYTES = 4 << 20


def _block_matrix(op, width: int) -> np.ndarray:
    """The (width, width) block-diagonal matrix applying ``op``'s block
    transform to every whole block of a row: the 8x8 basis for ``matmul8``,
    the one-hot ``M[idx[j], j] = 1`` for ``perm`` (exactly one nonzero term
    per output, so the matmul reproduces the gather bit for bit).  The zero
    weights mean a non-finite token turns its whole row into NaN."""
    if op.kind == "matmul8":
        m = np.asarray(op.params[0], np.float32)
    else:
        idx = np.asarray(op.params[0])
        m = np.zeros((len(idx), len(idx)), np.float32)
        m[idx, np.arange(len(idx))] = 1.0
    return np.kron(np.eye(width // m.shape[0], dtype=np.float32), m)


def _run_ops(regs, program, matrix_refs, delay=None):
    """The op list over the register file, in place; ``delay(op, x)`` gives
    a delay op's output (stateful programs only)."""
    bi = 0
    for op in program.ops:
        if op.kind in ("matmul8", "perm"):
            regs[op.out] = jnp.dot(
                regs[op.ins[0]], matrix_refs[bi][...],
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            bi += 1
        elif op.kind == "delay":
            regs[op.out] = delay(op, regs[op.ins[0]])
        else:
            regs[op.out] = apply_op(
                op.kind, op.params, [regs[j] for j in op.ins]
            )


def _stream_kernel(x_ref, *rest, program):
    # rest = (*matrix_refs, o_ref): the block-diagonal transform matrices
    # ride in as operands because Pallas kernels may not capture array
    # constants.
    matrix_refs, o_ref = rest[:-1], rest[-1]
    regs = [None] * program.n_regs
    for i in range(program.n_inputs):
        regs[i] = x_ref[i]
    _run_ops(regs, program, matrix_refs)
    for j, r in enumerate(program.outputs):
        o_ref[j] = regs[r]


def _max_all(v):
    """(rows, W) -> (1, 1) maximum, one axis at a time."""
    return jnp.max(jnp.max(v, axis=0, keepdims=True), axis=1, keepdims=True)


def _stateful_kernel(x_ref, s_ref, c_ref, *rest, program, tile_tokens):
    """One row tile of a stateful program.  The grid walks a call's tiles
    in order ("arbitrary"), and the (2, S) carry block stays resident
    across them, written back once: lane j of row 0 holds register j (the
    delay's input at the last valid token so far: the new state), row 1
    the delay's input at the previous tile's last token, valid or not, so
    that a tile's first output is the token before it, as in ``delay_ref``.
    ``c_ref`` row m holds input m's valid-token count in every lane."""
    matrix_refs, o_ref, so_ref = rest[:-2], rest[-2], rest[-1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        so_ref[...] = jnp.broadcast_to(s_ref[...], so_ref.shape)

    carry = so_ref[...]
    shape = x_ref.shape[1:]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    base = i * tile_tokens
    pos = base + row * shape[1] + col
    slot_lane = jax.lax.broadcasted_iota(jnp.int32, carry.shape, 1)
    carry_row = jax.lax.broadcasted_iota(jnp.int32, carry.shape, 0)
    new_carry = [carry]

    def delay(op, x):
        slot, mask_input = op.params
        # the slot's carries as (1, 1) vectors: a max over one live lane is
        # exact (and keeps -0.0, which a masked sum would not)
        lane = jnp.max(jnp.where(slot_lane == slot, carry, -jnp.inf),
                       axis=1, keepdims=True)
        s, prev = (
            jnp.max(jnp.where(carry_row[:, :1] == r, lane, -jnp.inf),
                    axis=0, keepdims=True)
            for r in (0, 1)
        )
        a = pltpu.roll(x, 1, 1)          # a[r, c] = x[r, c - 1 mod W]
        b = pltpu.roll(a, 1, 0)          # b[r, 0] = x[r - 1, W - 1]
        y = jnp.where(pos == base, prev, jnp.where(col == 0, b, a))
        last = jnp.minimum(c_ref[mask_input:mask_input + 1, :],
                           base + tile_tokens) - 1
        hit = pos == last
        v = _max_all(jnp.where(hit, x, -jnp.inf))
        found = _max_all(jnp.where(hit, 1.0, 0.0)) > 0.0
        # b[0, 0] is the tile's last token
        end = _max_all(jnp.where((row == 0) & (col == 0), b, -jnp.inf))
        new_carry[0] = jnp.where(
            slot_lane == slot,
            jnp.where(carry_row == 0, jnp.where(found, v, s), end),
            new_carry[0],
        )
        return y

    regs = [None] * program.n_regs
    for k in range(program.n_inputs):
        regs[k] = x_ref[k]
    _run_ops(regs, program, matrix_refs, delay)
    for j, r in enumerate(program.outputs):
        o_ref[j] = regs[r]
    so_ref[...] = new_carry[0]


def _rows_per_tile(rows: int, width: int, program) -> int:
    """Rows per grid step: the whole row axis when it fits the VMEM budget
    (a block dim equal to the array dim is always legal), else the largest
    multiple of 8 that does."""
    live = 2 * (program.n_inputs + len(program.outputs)) + program.n_regs
    fit = _TILE_BUDGET_BYTES // (4 * width * max(live, 1))
    fit = max(_SUBLANES, fit - fit % _SUBLANES)
    return rows if rows <= fit else fit


def fused_stream_fwd(
    stack: jax.Array,  # (n_in, N) or (n_in, B, N) float32 wire stack
    program,
    *,
    state=None,
    counts=None,
    interpret: bool = False,
):  # (n_out, N) / (n_out, B, N); with state, (that, new state)
    """One Pallas launch per call, batched or not.

    Leading axes between the wire axis and the token axis (B sessions, or
    the k chunks of a flat megastep) fold into the row axis, so B rows cost
    ONE kernel launch, not B.  Each row is padded with zeros to a multiple
    of ``row_width(program)``; a block transform's blocks therefore never
    straddle a row (or a session, or a chunk) as long as ``N`` is a
    multiple of the transform's block size — otherwise the call is refused.
    A stateful program takes its ``(n_state,)`` registers and per-input
    valid ``counts`` and returns the new registers too (``_stateful_fwd``).
    """
    n_in, *lead, n = stack.shape
    unit = transform_unit(program)
    if n % unit:
        raise GraphError(
            f"stream kernel: {n} tokens per row is not a multiple of the "
            f"region's block transform size {unit}"
        )
    width = row_width(program)
    if program.n_state:
        return _stateful_fwd(stack, program, state, counts, width, interpret)
    n_pad = -n % width
    if n_pad:
        stack = jnp.pad(stack, [(0, 0)] * (stack.ndim - 1) + [(0, n_pad)])
    rows = math.prod(lead) * (n + n_pad) // width
    x = stack.reshape(n_in, rows, width)
    tile = _rows_per_tile(rows, width, program)
    r_pad = -rows % tile
    if r_pad:
        x = jnp.pad(x, ((0, 0), (0, r_pad), (0, 0)))
    matrices = [
        jnp.asarray(_block_matrix(op, width))
        for op in program.ops if op.kind in ("matmul8", "perm")
    ]
    n_out = len(program.outputs)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, program=program),
        grid=((rows + r_pad) // tile,),
        in_specs=[pl.BlockSpec((n_in, tile, width), lambda i: (0, i, 0))]
        + [pl.BlockSpec(m.shape, lambda i: (0, 0)) for m in matrices],
        out_specs=pl.BlockSpec((n_out, tile, width), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_out, rows + r_pad, width), jnp.float32
        ),
        interpret=interpret,
        name="stream_fused",
    )(x, *matrices)
    out = out[:, :rows].reshape(n_out, *lead, n + n_pad)
    return out[..., :n] if n_pad else out


def _stateful_fwd(stack, program, state, counts, width, interpret):
    """A stateful program's call: every token of the call is one stream in
    order, so the leading axes flatten *before* the row padding (padding
    only at the end), and the grid walks row tiles in order, carrying each
    register from tile to tile.  Under the serve batcher's vmap each
    session lane is its own call (``pallas_call`` batches it as a parallel
    grid axis)."""
    n_in, *lead, n = stack.shape
    tokens = math.prod(lead) * n
    x = stack.reshape(n_in, tokens)
    rows = -(-tokens // width)
    fit = _rows_per_tile(rows, width, program)
    tiles = -(-rows // fit)
    # even tiles, each a multiple of 8 rows: the padding stays under 8 rows
    # a tile where the stateless path would pad up to a whole tile
    tile = rows if tiles == 1 else -(-rows // (tiles * _SUBLANES)) * _SUBLANES
    x = jnp.pad(x, ((0, 0), (0, tiles * tile * width - tokens)))
    x = x.reshape(n_in, tiles * tile, width)
    lanes = -(-program.n_state // LANES) * LANES
    s = jnp.pad(jnp.asarray(state, jnp.float32).reshape(-1),
                (0, lanes - program.n_state)).reshape(1, lanes)
    c = jnp.broadcast_to(
        jnp.asarray(counts, jnp.int32).reshape(n_in, 1), (n_in, width)
    )
    matrices = [
        jnp.asarray(_block_matrix(op, width))
        for op in program.ops if op.kind in ("matmul8", "perm")
    ]
    n_out = len(program.outputs)
    fixed = lambda i: (0, 0)  # noqa: E731 — resident across the tiles
    out, new_state = pl.pallas_call(
        functools.partial(_stateful_kernel, program=program,
                          tile_tokens=tile * width),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((n_in, tile, width), lambda i: (0, i, 0)),
                  pl.BlockSpec((1, lanes), fixed),
                  pl.BlockSpec((n_in, width), fixed)]
        + [pl.BlockSpec(m.shape, fixed) for m in matrices],
        out_specs=[pl.BlockSpec((n_out, tile, width), lambda i: (0, i, 0)),
                   pl.BlockSpec((2, lanes), fixed)],
        out_shape=[
            jax.ShapeDtypeStruct((n_out, tiles * tile, width), jnp.float32),
            jax.ShapeDtypeStruct((2, lanes), jnp.float32),
        ],
        interpret=interpret,
        name="stream_fused",
    )(x, s, c, *matrices)
    out = out.reshape(n_out, -1)[:, :tokens].reshape(n_out, *lead, n)
    return out, new_state[0, :program.n_state]
