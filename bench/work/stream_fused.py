"""The algorithm's operations and bytes per token of a fused stream-region
call (`repro.kernels.stream_fused`).

The count is the algorithm's, not the implementation's: the kernel applies
`matmul8` as one block-diagonal (row, row) matmul per lane-dense row, which
does 16x the multiply-adds of an 8-point transform at row width 128 and more
at wider rows.  Counting that would make a cheaper implementation read as a
lower roofline share.  So:

  bytes     4 per input wire and 4 per output wire, per token (float32)
  matmul8   16 ops per token (8 multiply-adds)
  perm      0 ops (a reorder)
  affine    one op per non-identity component of (x + pre) * mul + post
  clip      2 ops (max, min)
  axpy      2 ops (multiply, add)
  min2/max2 1 op
  const     0 ops

An op is given as a sequence `(kind, *params)` or as any object with
`kind` and `params` attributes (the program's `StreamOp`).  Nothing here
depends on the row width the kernel picks.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES_PER_WIRE_TOKEN = 4


def _kind_params(op) -> Tuple[str, tuple]:
    if hasattr(op, "kind"):
        return op.kind, tuple(op.params)
    kind, *params = op
    return kind, tuple(params)


def op_count(kind: str, params: tuple) -> int:
    """Arithmetic operations one token costs in one op of the region."""
    if kind == "matmul8":
        return 16
    if kind in ("perm", "const"):
        return 0
    if kind == "affine":
        pre, mul, post = params
        return int(pre != 0.0) + int(mul != 1.0) + int(post != 0.0)
    if kind in ("clip", "axpy"):
        return 2
    if kind in ("min2", "max2"):
        return 1
    raise ValueError(f"unknown stream op {kind!r}")


def per_token(ops: Iterable, in_wires: int, out_wires: int) -> Tuple[int, int]:
    """(operations, bytes) one token of a region call costs."""
    n_ops = sum(op_count(*_kind_params(op)) for op in ops)
    return n_ops, BYTES_PER_WIRE_TOKEN * (in_wires + out_wires)


def call_work(ops: Iterable, in_wires: int, out_wires: int,
              tokens: int) -> Tuple[int, int]:
    """(operations, bytes) of calls over ``tokens`` tokens in all, padding
    lanes included (the batcher's padding shows in ``lanes_per_dispatch``)."""
    n_ops, n_bytes = per_token(ops, in_wires, out_wires)
    return n_ops * tokens, n_bytes * tokens
