"""SPMD features that need >1 device: run in a subprocess with 8 fake devices.

Covers: GPipe pipeline == sequential reference; int8 all-reduce over an axis;
sharded train step on a 2x2 mesh runs and matches the single-device loss.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    out = {}

    def auto_mesh(shape, axes):
        return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

    # ---- gpipe vs sequential ----
    from repro.distributed.pipeline import gpipe_apply, stack_stage_params
    mesh = auto_mesh((4,), ("stage",))
    key = jax.random.PRNGKey(0)
    per_stage = []
    for i in range(4):
        k1, k2, key = jax.random.split(key, 3)
        per_stage.append({"w": jax.random.normal(k1,(16,16))*0.3,
                          "b": jax.random.normal(k2,(16,))*0.1})
    params = stack_stage_params(per_stage)
    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])
    x = jax.random.normal(key, (6, 5, 16))
    with mesh:
        got = gpipe_apply(stage_fn, params, x, mesh=mesh, axis="stage")
    ref = x
    for p in per_stage:
        ref = jnp.tanh(ref @ p["w"] + p["b"])
    out["gpipe_err"] = float(jnp.max(jnp.abs(got - ref)))

    # ---- int8 all-reduce over an axis ----
    from repro.distributed.compression import all_reduce_int8
    mesh2 = auto_mesh((8,), ("d",))
    y = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 128))
    f = jax.shard_map(lambda a: all_reduce_int8(a[0], "d")[None],
                      mesh=mesh2, in_specs=P("d"), out_specs=P("d"),
                      check_vma=False)
    with mesh2:
        red = f(y)
    true = jnp.sum(y, 0, keepdims=True)
    rel = float(jnp.linalg.norm(red[0] - true[0]) / jnp.linalg.norm(true[0]))
    out["int8_allreduce_rel"] = rel

    # ---- sharded train step on 2x4 mesh matches 1-device loss ----
    from repro.configs import get_config
    from repro.distributed.sharding import make_rules, shard_ctx
    from repro.launch.steps import make_train_step, params_specs, specs_to_pspecs, batch_specs, opt_specs
    from repro.model import lm
    from repro.optim import OptConfig, init_opt_state
    cfg = get_config("smollm-135m").reduced()
    opt = OptConfig()
    mesh3 = auto_mesh((2, 4), ("data", "model"))
    rules = make_rules(cfg, mesh3)
    key = jax.random.PRNGKey(0)
    params = lm.init_model(cfg, key)
    opt_state = init_opt_state(params, opt)
    B, S = 4, 64
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    step = make_train_step(cfg, opt, 1)
    def traced(p, o, b):
        with shard_ctx(mesh3, rules):
            return step(p, o, b)
    with mesh3:
        p_specs, p_log = params_specs(cfg)
        in_sh = specs_to_pspecs(p_specs, p_log, mesh3, rules)
        sharded_params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh3, s), in_sh))
        _, _, m_sharded = jax.jit(traced)(sharded_params, opt_state, batch)
    m_single = step(params, opt_state, batch)[2]
    out["loss_sharded"] = float(m_sharded["loss"])
    out["loss_single"] = float(m_single["loss"])
    print("RESULT " + json.dumps(out))
    """
)


def test_spmd_features():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["gpipe_err"] < 1e-5
    assert out["int8_allreduce_rel"] < 0.02
    assert abs(out["loss_sharded"] - out["loss_single"]) < 0.05
