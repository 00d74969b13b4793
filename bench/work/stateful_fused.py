"""The algorithm's operations and bytes of a stateful fused stream-region
call: the stateless count of ``bench/work/stream_fused.py`` plus the state.

  delay     0 ops (a move through a register)
  bytes     4 per input and output wire per token, plus each lane's state
            registers read and written once per call (4 bytes each way)

Every other op counts as in ``stream_fused``.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench.work import stream_fused


def op_count(kind: str, params: tuple) -> int:
    """Arithmetic operations one token costs in one op of the region."""
    return 0 if kind == "delay" else stream_fused.op_count(kind, params)


def call_work(ops: Iterable, in_wires: int, out_wires: int, tokens: int,
              lanes: int, state_registers: int) -> Tuple[int, int]:
    """(operations, bytes) of calls over ``tokens`` tokens and ``lanes``
    session lanes in all, padding included."""
    n_ops = sum(op_count(*stream_fused._kind_params(op)) for op in ops)
    state_bytes = 2 * stream_fused.BYTES_PER_WIRE_TOKEN * state_registers
    wire_bytes = stream_fused.BYTES_PER_WIRE_TOKEN * (in_wires + out_wires)
    return n_ops * tokens, wire_bytes * tokens + state_bytes * lanes
