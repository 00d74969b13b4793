"""FIR29 low-pass reference: sink[n] = sum_i h_i x[n - i] with zero initial
state (numpy's full convolution, cut to the input's length), and
xsink[n] = x[n - 29], zeros first.

``fir1_taps`` gives MATLAB's ``fir1(order, cutoff)`` with its default
Hamming window, the design of the CMSIS-DSP example's coefficients.  The
configuration states float32 elementwise arithmetic, so the control runs
the systolic chain with every sample, product and sum in bfloat16.
"""

from __future__ import annotations

import numpy as np

from bench.reference import bf16


def fir1_taps(order: int, cutoff: float) -> np.ndarray:
    """Hamming-windowed sinc low-pass, ``cutoff`` a fraction of Nyquist,
    normalised to unit gain at DC."""
    k = np.arange(order + 1)
    m = k - order / 2
    w = 0.54 - 0.46 * np.cos(2 * np.pi * k / order)
    safe = np.where(m == 0, 1.0, m)
    ideal = np.where(m == 0, cutoff,
                     np.sin(cutoff * np.pi * safe) / (np.pi * safe))
    h = w * ideal
    return h / h.sum()


def reference(config, x):
    x = np.asarray(x, np.float64)
    h = np.asarray(config["taps"], np.float64)
    delay = np.concatenate([np.zeros(len(h)), x])[:len(x)]
    return {"sink": np.convolve(x, h)[:len(x)], "xsink": delay}


def control(config, x):
    xb = bf16(np.asarray(x, np.float32))
    acc = np.zeros_like(xb)
    xd = xb
    for c in config["taps"]:
        acc = bf16(acc + bf16(bf16(np.float32(c)) * xd))
        xd = np.concatenate([np.zeros(1, np.float32), xd[:-1]])
    return {"sink": acc.astype(np.float64), "xsink": xd.astype(np.float64)}
