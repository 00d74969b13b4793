"""SPMD pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

The stage axis is a mesh axis (e.g. 'pod' across pods, or a dedicated 'stage'
axis); stage parameters are stacked on a leading dim sharded over that axis.
Each tick every stage computes its microbatch and the activations rotate one hop
with ``lax.ppermute`` (ICI/DCN neighbor exchange — the FIFO channel between
pipeline-stage "actors").  A schedule of n_micro + n_stages − 1 ticks drains the
pipe; bubbles are masked ticks, exactly the WAIT states of the pipeline's actor
machine (DESIGN.md §2).

The stage assignment itself (which layers land in which stage) comes from the
StreamBlocks partitioner (``core.partitioner.explore_lm`` — chain DP).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def stack_stage_params(per_stage: list) -> PyTree:
    """Stack a list of per-stage param pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def gpipe_apply(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,  # leaves: (n_stages, ...) sharded over `axis`
    x_micro: jax.Array,  # (n_micro, mb, ...) inputs to stage 0
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run the pipeline; returns (n_micro, mb, ...) outputs of the last stage."""
    n_stages = dict(mesh.shape)[axis]
    n_micro = x_micro.shape[0]
    assert n_micro >= 1
    ticks = n_micro + n_stages - 1

    other_axes = [a for a in mesh.axis_names if a != axis]

    def body(params, xm):
        p_local = jax.tree.map(lambda a: a[0], params)  # this stage's slice
        sidx = jax.lax.axis_index(axis)
        mb_shape = xm.shape[1:]
        buf0 = jnp.zeros(mb_shape, xm.dtype)

        def tick(buf, t):
            src = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            inp = jnp.where(sidx == 0, src, buf)
            y = stage_fn(p_local, inp)
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return nxt, y

        _, ys = jax.lax.scan(tick, buf0, jnp.arange(ticks))
        # last stage's outputs live at ticks [n_stages-1, ticks)
        outs = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, 0)
        # replicate the last stage's result across the stage axis
        outs = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)


def pipeline_bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble overhead: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
