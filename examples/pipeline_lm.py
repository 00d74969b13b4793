"""Pipeline-parallel LM forward: the partitioner's chain-DP stage plan executed
with the GPipe SPMD pipeline over a 'stage' mesh axis.

A reduced smollm runs its transformer blocks as 4 pipeline stages (stage
assignment from ``explore_lm``'s optimal contiguous split); the pipelined
forward is verified to match the plain sequential forward exactly.

    PYTHONPATH=src python examples/pipeline_lm.py
(needs >1 device; re-execs itself with 8 fake CPU devices)
"""

import os
import sys
from pathlib import Path

if os.environ.get("XLA_FLAGS") is None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.partitioner import explore_lm
from repro.distributed.pipeline import gpipe_apply
from repro.model import lm
from repro.model.blocks import block_fwd


def main():
    cfg = get_config("smollm-135m").reduced()  # 2 layers/period... use 8 blocks
    import dataclasses

    cfg = dataclasses.replace(cfg, num_layers=8)
    n_stages = 4
    mesh = jax.make_mesh(
        (n_stages,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    params = lm.init_model(cfg, jax.random.PRNGKey(0))

    # 1) the partitioner's stage plan (chain DP over per-layer costs)
    plans = explore_lm(
        cfg, seq_len=64, global_batch=8, total_chips=n_stages,
        stage_options=(n_stages,),
    )
    plan = plans[0]
    blocks_per_stage = n_stages and cfg.num_layers // n_stages
    print(f"chain-DP stage map (embed..blocks..head): {plan.stage_of_layer}")

    # 2) execute: blocks stacked per stage, embed/head outside the pipe
    B, S = 8, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.arange(S, dtype=jnp.int32)
    kind = cfg.block_kind(0)

    # per-stage params: contiguous blocks_per_stage blocks each
    layer_p = params["layers"]["pos0"]  # leaves (num_layers, ...)
    stage_params = jax.tree.map(
        lambda a: a.reshape(n_stages, blocks_per_stage, *a.shape[1:]), layer_p
    )

    def stage_fn(pstage, xin):
        def body(x, pslice):
            y, _, _ = block_fwd(pslice, x, kind, cfg, positions)
            return y, None

        out, _ = jax.lax.scan(body, xin, pstage)
        return out

    n_micro = 4
    xm = x.reshape(n_micro, B // n_micro, S, cfg.d_model)
    with mesh:
        y_pipe = gpipe_apply(stage_fn, stage_params, xm, mesh=mesh, axis="stage")
    y_pipe = y_pipe.reshape(B, S, cfg.d_model)

    # 3) sequential reference
    def seq_body(x, pslice):
        y, _, _ = block_fwd(pslice, x, kind, cfg, positions)
        return y, None

    y_ref, _ = jax.lax.scan(seq_body, x, layer_p)

    err = float(jnp.max(jnp.abs(y_pipe.astype(jnp.float32) - y_ref.astype(jnp.float32))))
    print(f"pipelined forward vs sequential: max_err={err:.2e}")
    assert err < 1e-2, "pipeline does not match sequential execution"
    from repro.distributed.pipeline import pipeline_bubble_fraction

    print(
        f"stages={n_stages} microbatches={n_micro} "
        f"bubble={pipeline_bubble_fraction(n_micro, n_stages):.0%} "
        f"-> MATCH"
    )


if __name__ == "__main__":
    main()
