"""The fused stream kernel compiles for a TPU v5e chip (described, not attached).

The TPU compiler is installed even where no chip is: these tests lower and
compile ``fused_stream_fwd`` for a described ``v5e:2x2`` topology, which
refuses what interpret mode accepts — tiles off the (8, 128) rule, shape
casts Mosaic cannot lower, too much VMEM.  A passing compile is not a chip
run: nothing executes, so nothing here says anything about results or time.

The topology is described inside a module fixture only: only one process
may load the TPU library at a time, and every pytest worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.streams import _IDCT_BASIS, _ZIGZAG_INV
from repro.kernels.stream_fused import StreamOp, StreamProgram
from repro.kernels.stream_fused.kernel import fused_stream_fwd
from repro.kernels.stream_fused.ops import transform_unit

N = 4096  # one staged block at the chip-scale block size
K = 4     # chunks per flat megastep (megastep="auto")
PERM24 = (3, 0, 1, 2, 7, 4, 5, 6, 11, 8, 9, 10, 15, 12, 13, 14, 19, 16, 17,
          18, 23, 20, 21, 22)

PROGRAMS = {
    "affine": StreamProgram(
        1, 2, (StreamOp("affine", (0,), 1, (-128.0, 0.125, 1.0)),), (1,)),
    "clip": StreamProgram(
        1, 2, (StreamOp("clip", (0,), 1, (-256.0, 255.0)),), (1,)),
    "matmul8": StreamProgram(
        1, 2, (StreamOp("matmul8", (0,), 1, (_IDCT_BASIS,)),), (1,)),
    "perm": StreamProgram(
        1, 2, (StreamOp("perm", (0,), 1, (_ZIGZAG_INV,)),), (1,)),
    "min2_max2": StreamProgram(
        2, 4, (StreamOp("min2", (0, 1), 2), StreamOp("max2", (0, 1), 3)),
        (2, 3)),
    "axpy_const": StreamProgram(
        1, 4, (StreamOp("const", (0,), 1, (0.0,)),
               StreamOp("axpy", (0, 1), 2, (0.25,)),
               StreamOp("axpy", (0, 2), 3, (-0.5,))), (0, 3)),
    # a 24-point perm widens rows to lcm(128, 24) = 384 tokens: matmul8
    # then runs once per 128 lanes of the row
    "matmul8_perm24": StreamProgram(
        1, 3, (StreamOp("matmul8", (0,), 1, (_IDCT_BASIS,)),
               StreamOp("perm", (1,), 2, (PERM24,))), (2,)),
    # the IDCT8 region as fusion emits it: descale -> idct -> clip
    "idct8_region": StreamProgram(
        1, 4, (StreamOp("affine", (0,), 1, (-128.0, 0.125, 0.0)),
               StreamOp("matmul8", (1,), 2, (_IDCT_BASIS,)),
               StreamOp("clip", (2,), 3, (-256.0, 255.0))), (3,)),
}

SHAPES = {  # wire shape for n tokens per block
    "plain": lambda n: (n,),
    "batched": lambda n: (8, n),        # 8 serve sessions' lanes, one launch
    "flat_megastep": lambda n: (K * n,),  # k chunks flattened into one grid
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return jax.jit(fn).lower(x).compile()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("op", sorted(PROGRAMS))
def test_stream_kernel_compiles_for_v5e(op, shape, one_chip):
    prog = PROGRAMS[op]
    n = N - N % transform_unit(prog)  # a block holds whole transform blocks
    full = (prog.n_inputs,) + SHAPES[shape](n)
    compiled = _compile(
        lambda s: fused_stream_fwd(s, prog), full, one_chip
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["matmul8", "perm", "min2_max2"])
def test_stream_kernel_compiles_under_vmap(op, one_chip):
    """The serve batcher vmaps the device step over session lanes, so the
    kernel is batched by ``pallas_call``'s own vmap rule."""
    prog = PROGRAMS[op]

    def lanes(s):  # s: (B, n_in, K, N) — B lanes of flat-megastep stacks
        return jax.vmap(lambda x: fused_stream_fwd(x, prog))(s)

    compiled = _compile(lanes, (8, prog.n_inputs, K, N), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_block_transform_smaller_than_a_row_pads(one_chip):
    """A block of 64 tokens (the CPU tests' size) still compiles: rows are
    zero-padded to the 128 lanes."""
    prog = PROGRAMS["perm"]
    compiled = _compile(
        lambda s: fused_stream_fwd(s, prog), (1, 3, 64), one_chip
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_directory(env_dir, monkeypatch, tmp_path):
    """Chip entry points cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else in the fixed directory they name — never a generated one.  The
    config writes are recorded, not applied: tests keep the cache off."""
    from repro.runtime import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    written = {}
    monkeypatch.setattr(jax.config, "update", written.__setitem__)
    want = env_dir or str(tmp_path / ".jax_cache")
    assert compile_cache.enable(tmp_path / ".jax_cache") == want
    assert written["jax_compilation_cache_dir"] == want
    assert written["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_torn_transform_block_is_refused():
    from repro.core.graph import GraphError

    with pytest.raises(GraphError, match="block transform"):
        jax.eval_shape(
            lambda s: fused_stream_fwd(s, PROGRAMS["perm"]),
            jax.ShapeDtypeStruct((1, 100), np.float32),
        )


def _fir_program(taps=29):
    """The stateful FIR region as fusion emits it: seed, then per tap a
    delay through its register and an axpy."""
    ops = [StreamOp("const", (0,), 1, (0.0,))]
    x, acc, n = 0, 1, 2
    for t in range(taps):
        ops.append(StreamOp("delay", (x,), n, (t, 0)))
        ops.append(StreamOp("axpy", (x, acc), n + 1, (0.01 * (t + 1),)))
        x, acc, n = n, n + 1, n + 2
    return StreamProgram(1, n, tuple(ops), (acc, x), n_state=taps)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_stateful_stream_kernel_compiles_for_v5e(shape, one_chip):
    prog = _fir_program()
    lanes = 32
    full = (lanes, prog.n_inputs) + SHAPES[shape](N)

    def call(x, state, counts):  # one lane per session, as the batcher vmaps
        return jax.vmap(lambda v, s, c: fused_stream_fwd(
            v, prog, state=s, counts=c))(x, state, counts)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            ((full, jnp.float32), ((lanes, prog.n_state), jnp.float32),
             ((lanes, 1), jnp.int32))]
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
