"""Plain references, one module per configuration's ``reference``.

Each module gives ``reference(config, x)`` and ``control(config, x)``:
the network's outputs per egress port for one session's input ``x``
(float64 samples).  ``reference`` computes in float64 with numpy;
``control`` is the same algorithm one precision step below what the
configuration states, the step a later change might be tempted to take.
Neither imports the program or takes anything it made.
"""

from __future__ import annotations

import numpy as np


def bf16(a) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), kept in
    float32."""
    u = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    r = ((u + 0x7FFF + ((u >> 16) & 1)) >> 16) << 16
    return r.astype(np.uint32).view(np.float32)


def matmul_bf16_3x(x, w) -> np.ndarray:
    """``x @ w`` as matmul precision HIGH computes it: each float32 operand
    split into a bfloat16 high and low part, three bfloat16 products
    (hi*hi + hi*lo + lo*hi) accumulated in float32."""
    x = np.asarray(x, np.float32)
    w = np.asarray(w, np.float32)
    xh, wh = bf16(x), bf16(w)
    xl, wl = bf16(x - xh), bf16(w - wh)
    return (xh @ wh + xh @ wl + xl @ wh).astype(np.float32)
