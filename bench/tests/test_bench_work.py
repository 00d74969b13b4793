"""The algorithm's work per token of a fused stream-region call."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import stats
from bench.work.stream_fused import call_work, op_count, per_token

ROOT = Path(__file__).resolve().parents[2]


def _program(ops):
    from repro.kernels.stream_fused.ops import StreamOp, StreamProgram

    prog_ops = tuple(StreamOp(k, (i,), i + 1, p) for i, (k, p) in
                     enumerate(ops))
    return StreamProgram(1, len(ops) + 1, prog_ops, (len(ops),))


def test_matmul8_counts_the_transform_not_the_block_diagonal():
    from repro.kernels.stream_fused.ops import row_width

    basis = np.eye(8, dtype=np.float32)
    only = _program([("matmul8", (basis,))])
    assert row_width(only) == 128       # the kernel's 128x128 block matmul
    assert per_token(only.ops, 1, 1) == (16, 8)   # not 2 * 128 = 256


def test_count_does_not_depend_on_row_width():
    from repro.kernels.stream_fused.ops import row_width

    basis = np.eye(8, dtype=np.float32)
    narrow = _program([("matmul8", (basis,)),
                       ("perm", (np.arange(64)[::-1],))])
    wide = _program([("matmul8", (basis,)),
                     ("perm", (np.arange(384)[::-1],))])
    assert row_width(narrow) == 128 and row_width(wide) == 384
    assert per_token(narrow.ops, 1, 1) == per_token(wide.ops, 1, 1) == (16, 8)


@pytest.mark.parametrize("op,count", [
    (("affine", -128.0, 0.125, 0.0), 2), (("affine", 0.0, 1.0, 3.0), 1),
    (("clip", -1.0, 1.0), 2), (("axpy", 0.5), 2), (("const", 0.0), 0),
    (("perm", [1, 0]), 0), (("min2",), 1), (("max2",), 1),
])
def test_elementwise_counts(op, count):
    assert op_count(op[0], tuple(op[1:])) == count


def test_call_work_scales_with_tokens():
    ops = [["affine", -128.0, 0.125, 0.0], ["matmul8"], ["clip", -256.0, 255.0]]
    assert call_work(ops, 1, 1, 1000) == (20_000, 8_000)
    with pytest.raises(ValueError):
        op_count("gather", ())


@pytest.mark.parametrize("name", ["idct8", "fir32"])
def test_config_kernel_ops_are_the_networks(name):
    """The op list a configuration counts is the one its network fuses."""
    from bench import harness

    config = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                        .read_text())
    prog = harness.build_program(dict(config, block=256))
    (dp,) = prog.device_programs().values()
    (fused,) = dp.actors
    program = prog.module.actors[fused].impl.stream_program
    got = [(op.kind, [float(np.asarray(p).sum()) if hasattr(p, "shape")
                      else p for p in op.params]) for op in program.ops]
    want = [(k, list(p)) for k, *p in config["kernel"]["ops"]]
    assert [k for k, _ in got] == [k for k, _ in want]
    for (kind, gp), (_, wp) in zip(got, want):
        if kind != "matmul8":
            assert gp == pytest.approx(wp)
    assert len(program.outputs) == config["kernel"]["out_wires"]
    assert program.n_inputs == config["kernel"]["in_wires"]


def test_percentile_is_exact_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, float("inf")], 95) == float("inf")
    assert stats.percentile(list(range(20, 0, -1)), 50) == 10
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1], 0)


def test_spread_is_iqr_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_peaks_lookup():
    from bench import harness

    v5e = harness.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
