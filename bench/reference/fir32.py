"""FIR32 reference, as the network authors it: a chain of 32 taps
acc = acc + c_i * x starting from acc = 0, with x forwarded unchanged (no
delay line), so sink = x * sum(c_i) in tap order and xsink = x.

The configuration states float32 elementwise arithmetic, so the control
computes every product and sum in bfloat16.
"""

from __future__ import annotations

import numpy as np

from bench.reference import bf16


def reference(config, x):
    x = np.asarray(x, np.float64)
    acc = np.zeros_like(x)
    for c in config["taps"]:
        acc = acc + float(c) * x
    return {"sink": acc, "xsink": x.copy()}


def control(config, x):
    xb = bf16(np.asarray(x, np.float32))
    acc = np.zeros_like(xb)
    for c in config["taps"]:
        acc = bf16(acc + bf16(bf16(np.float32(c)) * xb))
    return {"sink": acc.astype(np.float64), "xsink": xb.astype(np.float64)}
