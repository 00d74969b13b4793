"""SDF region fusion: golden equivalence (fused ≡ unfused ≡ host) on all four
Table-I networks, the device dynamic-rate mask path, the Pallas stream kernel
vs its jnp reference, and the opt-level-2 folder."""

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.apps.streams import NETWORKS
from repro.kernels.stream_fused import (
    StreamOp,
    StreamProgram,
    fold,
    fused_stream,
)
from repro.kernels.stream_fused.ref import fused_stream_ref
from repro.runtime.device_runtime import compile_partition

from helpers import make_topfilter, topfilter_expected

SIZES = {"TopFilter": 1200, "FIR32": 600, "Bitonic8": 48, "IDCT8": 48,
         "ZigZag": 12}


def _run(net, got, **compile_kw):
    prog = repro.compile(net, **compile_kw)
    prog.run()
    return list(got), prog


# ---------------------------------------------------------------------------
# Golden: fused ≡ unfused ≡ host on every benchmark network
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NETWORKS))
def test_fusion_golden(name):
    size = SIZES[name]
    builder = NETWORKS[name]
    net, got = builder(size) if name != "FIR32" else builder(n=size)

    host, _ = _run(net, got, backend="host")
    unfused, up = _run(net, got, backend="device", block=256, fuse=False)
    fused, fp = _run(net, got, backend="device", block=256)

    assert len(host) == len(unfused) == len(fused)
    # fusion is bit-preserving at the default opt level
    assert fused == unfused
    # device float32 vs host python-float math: numerically equal
    np.testing.assert_allclose(fused, host, rtol=1e-5, atol=1e-4)

    # fusion actually happened on the multi-actor SDF networks
    n_unfused = len(up.device_program().actors)
    n_fused = len(fp.device_program().actors)
    if name == "TopFilter":  # single dynamic actor: nothing to fuse
        assert n_fused == n_unfused == 1
    else:
        assert n_fused < n_unfused
        assert any(a.startswith("fused") for a in fp.device_program().actors)


@pytest.mark.parametrize("name", ["FIR32", "IDCT8"])
def test_fusion_opt2_allclose(name):
    """opt_level=2 folding is value-changing but numerically tight."""
    size = SIZES[name]
    builder = NETWORKS[name]
    net, got = builder(size) if name != "FIR32" else builder(n=size)
    unfused, _ = _run(net, got, backend="device", block=256, fuse=False)
    opt2, _ = _run(net, got, backend="device", block=256, opt_level=2)
    np.testing.assert_allclose(opt2, unfused, rtol=1e-4, atol=1e-4)


def test_fused_codegen_is_pallas_for_spec_networks():
    net, _ = NETWORKS["IDCT8"](16)
    prog = repro.compile(net, backend="device", block=64)
    fused = prog.module.meta["fused"]
    assert all(v["codegen"] == "pallas" for v in fused.values())


# ---------------------------------------------------------------------------
# Device dynamic-rate mask path (Filter-style actors)
# ---------------------------------------------------------------------------


def test_device_mask_partial_block():
    """A partially-valid staged block: the dynamic filter must intersect its
    keep-predicate with the input validity mask, not overwrite it."""
    g, _ = make_topfilter(n=64, vectorized=True)
    prog = compile_partition(g, ["filter"], block=16, donate=False)
    vals = jnp.arange(16, dtype=jnp.float32) * 10.0  # 0,10,..,150
    mask = jnp.arange(16) < 10  # only first 10 lanes valid
    _, outs, idle = prog.step(
        prog.init_state, {"filter.IN": (vals, mask)}
    )
    ovals, omask = outs["filter.OUT"]
    expect = np.asarray(mask) & (np.asarray(vals) < 50)
    np.testing.assert_array_equal(np.asarray(omask), expect)
    # kept values are the valid ones below the threshold
    np.testing.assert_array_equal(
        np.asarray(ovals)[np.asarray(omask)], [0.0, 10.0, 20.0, 30.0, 40.0]
    )
    assert not bool(idle)  # tokens were consumed


def test_device_mask_empty_block_idles():
    g, _ = make_topfilter(n=64, vectorized=True)
    prog = compile_partition(g, ["filter"], block=8, donate=False)
    _, outs, idle = prog.step(
        prog.init_state,
        {"filter.IN": (jnp.zeros(8, jnp.float32), jnp.zeros(8, bool))},
    )
    assert bool(idle)
    assert not bool(outs["filter.OUT"][1].any())


def test_device_filter_end_to_end_matches_host():
    """Full hetero run with the dynamic-rate actor on the device."""
    g, got = make_topfilter(n=2000, vectorized=True)
    prog = repro.compile(g, backend="device", block=256)
    prog.run()
    assert got == topfilter_expected(n=2000)


def test_mixed_placement_fused_matches_host():
    """Mixed XCF (two host threads + accel) through the same pipeline."""
    from repro.core.xcf import make_xcf

    net, got = NETWORKS["FIR32"](n=400)
    g = net.graph()
    assignment = {}
    for a, act in g.actors.items():
        assignment[a] = "accel" if act.device_ok else (
            "t0" if a == "source" else "t1"
        )
    xcf = make_xcf(g.name, assignment)
    host, _ = _run(net, got, backend="host")
    prog = repro.compile(net, xcf, block=128)
    assert prog.hw_partition == "accel"
    assert len(prog.module.sw_regions()) == 2
    prog.run()
    mixed = list(got)
    np.testing.assert_allclose(mixed, host, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas stream kernel vs jnp reference
# ---------------------------------------------------------------------------


def _demo_program() -> StreamProgram:
    basis = np.linalg.qr(np.random.default_rng(0).normal(size=(8, 8)))[0]
    ops = (
        StreamOp("affine", (0,), 2, (-1.5, 0.25, 3.0)),
        StreamOp("matmul8", (2,), 3, (basis.astype(np.float32),)),
        StreamOp("const", (1,), 4, (0.0,)),
        StreamOp("axpy", (3, 4), 5, (0.7,)),
        StreamOp("min2", (5, 1), 6),
        StreamOp("max2", (5, 1), 7),
        StreamOp("clip", (7,), 8, (-2.0, 2.0)),
    )
    return StreamProgram(n_inputs=2, n_regs=9, ops=ops, outputs=(6, 8))


@pytest.mark.parametrize("n", [64, 512])
def test_stream_kernel_matches_ref(n):
    rng = np.random.default_rng(1)
    ins = [jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
           for _ in range(2)]
    prog = _demo_program()
    ref = fused_stream_ref(ins, prog)
    pal = fused_stream(ins, prog, use="pallas")  # interpret mode on CPU
    for r, p in zip(ref, pal):
        np.testing.assert_allclose(
            np.asarray(p), np.asarray(r), rtol=1e-6, atol=1e-6
        )


def _perm_program() -> StreamProgram:
    from repro.apps.streams import _ZIGZAG_INV

    ops = (
        StreamOp("perm", (0,), 2, (_ZIGZAG_INV,)),
        StreamOp("affine", (2,), 3, (-128.0, 0.125, 1.0)),
        StreamOp("min2", (3, 1), 4),
        StreamOp("clip", (4,), 5, (-2.0, 2.0)),
    )
    return StreamProgram(n_inputs=2, n_regs=6, ops=ops, outputs=(2, 5))


@pytest.mark.parametrize("shape", [(64,), (4096,), (3, 192), (4, 1024)])
def test_stream_kernel_bitwise_without_matmul8(shape):
    """Every op but matmul8 is bitwise equal to the reference — the one-hot
    perm matmul included — over plain, padded and batched wire shapes."""
    rng = np.random.default_rng(2)
    ins = [jnp.asarray(rng.normal(size=shape).astype(np.float32) * 300)
           for _ in range(2)]
    prog = _perm_program()
    ref = fused_stream_ref(ins, prog)
    pal = fused_stream(ins, prog, use="pallas")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r))


def test_fold_preserves_values_and_shrinks():
    ops = (
        StreamOp("affine", (0,), 1, (0.0, 2.0, 1.0)),
        StreamOp("affine", (1,), 2, (-1.0, 0.5, 0.0)),
        StreamOp("const", (0,), 3, (0.0,)),
        StreamOp("axpy", (2, 3), 4, (0.25,)),
        StreamOp("axpy", (2, 4), 5, (0.5,)),
        StreamOp("axpy", (2, 5), 6, (-0.125,)),
    )
    prog = StreamProgram(1, 7, ops, (6,))
    folded = fold(prog)
    assert len(folded.ops) < len(prog.ops)
    x = [jnp.linspace(-3, 3, 32, dtype=jnp.float32)]
    np.testing.assert_allclose(
        np.asarray(fused_stream_ref(x, folded)[0]),
        np.asarray(fused_stream_ref(x, prog)[0]),
        rtol=1e-6, atol=1e-6,
    )
