"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so importing
this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax import to
get 512 placeholder CPU devices; smoke tests and benchmarks see the real single
device and use ``make_test_mesh``.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """A mesh whose axes are all ``Auto``: the sharding rules here place
    arrays with ``with_sharding_constraint`` and let the compiler propagate,
    which ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh() -> Mesh:
    """1×1 mesh over however many local devices exist (usually 1 on CPU)."""
    n = jax.device_count()
    d = int(np.sqrt(n))
    while n % d:
        d -= 1
    return _auto_mesh((d, n // d), ("data", "model"))
