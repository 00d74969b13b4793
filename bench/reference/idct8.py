"""IDCT8 reference: y = clip(((x - 128) / 8) @ B, -256, 255) per 8-group.

B is the orthonormal 8-point DCT-III basis scaled by 1/2:
B[k, n] = c(k) cos(pi (n + 1/2) k / 8) / 2, c(0) = sqrt(1/2), else 1.
The configuration states float32 with the transform at matmul precision
HIGHEST, so the control computes the transform at HIGH (three bfloat16
passes) and the rest in float32.
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference import matmul_bf16_3x


def basis() -> np.ndarray:
    b = np.zeros((8, 8))
    for k in range(8):
        c = math.sqrt(0.5) if k == 0 else 1.0
        for n in range(8):
            b[k, n] = c * math.cos(math.pi * (n + 0.5) * k / 8.0) / 2.0
    return b


def reference(config, x):
    x = np.asarray(x, np.float64)
    y = ((x - 128.0) / 8.0).reshape(-1, 8) @ basis()
    return {"sink": np.clip(y, -256.0, 255.0).reshape(-1)}


def control(config, x):
    x = np.asarray(x, np.float32)
    d = (x - np.float32(128.0)) / np.float32(8.0)
    y = matmul_bf16_3x(d.reshape(-1, 8), basis())
    y = np.clip(y, np.float32(-256.0), np.float32(255.0))
    return {"sink": y.reshape(-1).astype(np.float64)}
