"""End-to-end behaviour: training improves + resumes, serving terminates on
idleness, the HLO analyzer multiplies loop bodies correctly."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.serve import run_serving
from repro.launch.train import run_training


def test_training_improves_and_survives_failure(tmp_path):
    out = run_training(
        "smollm-135m", steps=30, global_batch=8, seq_len=64,
        ckpt_dir=str(tmp_path), ckpt_every=10, fail_at=15, quiet=True,
    )
    assert out["steps"] == 30
    assert out["restarts"] == 1  # injected failure recovered via checkpoint
    assert out["improved"], (out["loss_first"], out["loss_last"])


def test_training_resume_continues(tmp_path):
    run_training(
        "smollm-135m", steps=10, global_batch=4, seq_len=32,
        ckpt_dir=str(tmp_path), ckpt_every=5, quiet=True,
    )
    out = run_training(
        "smollm-135m", steps=14, global_batch=4, seq_len=32,
        ckpt_dir=str(tmp_path), ckpt_every=5, quiet=True,
    )
    # resumed from step 10 -> only 4 fresh losses recorded
    assert len(out["losses"]) == 4


def test_serving_idleness_termination():
    out = run_serving(
        "smollm-135m", batch=2, prompt_len=8, max_new=6, quiet=True
    )
    assert out["output"].shape == (2, 6)
    assert 1 <= out["steps"] <= 6


def test_hlo_analysis_loop_multiplication():
    from repro.launch.hlo_analysis import analyze

    def f(x, w):
        def body(c, _):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jnp.zeros((64, 64), jnp.float32)
    w = jnp.zeros((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    st = analyze(txt)
    want = 7 * 2 * 64 * 64 * 64  # 7 loop iterations of a 64^3 matmul
    assert st.flops == pytest.approx(want, rel=0.05), (st.flops, want)


def test_hlo_analysis_collectives_on_spmd_program():
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((4,), ("d",), axis_types=(AxisType.Auto,))
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        sh = NamedSharding(mesh, P("d", None))
        def f(a):
            return jnp.sum(a * 2.0)
        comp = jax.jit(f, in_shardings=sh).lower(x).compile()
        st = analyze(comp.as_text())
        assert st.collective_bytes > 0, "expected an all-reduce"
        print("COLL_OK", st.collective_bytes)
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COLL_OK" in r.stdout
