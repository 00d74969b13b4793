"""The benchmark's networks keep the program's topology, its references
agree with the host interpreter, and each control fails its cell."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = {"idct8": "IDCT8", "fir32": "FIR32"}


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _shape(graph):
    actors = {
        n: (sorted((p.name, p.dtype) for p in a.inputs),
            sorted((p.name, p.dtype) for p in a.outputs),
            repr(getattr(a, "stream_op", None)))
        for n, a in graph.actors.items()
    }
    channels = sorted(c.key for c in graph.channels)
    return actors, channels


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_topology_matches_the_programs_network(name):
    from repro.apps.streams import NETWORKS

    config = _config(name)
    ours, _ = harness.builder(config).build(config, [1.0] * 64)
    theirs, _ = NETWORKS[CONFIGS[name]](64) if name == "idct8" else \
        NETWORKS[CONFIGS[name]](n=64)
    assert ours.graph().name == theirs.graph().name
    assert _shape(ours.graph()) == _shape(theirs.graph())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_equals_the_host_run(name):
    import repro

    config = _config(name)
    lo, hi = config["value_range"]
    x = np.random.default_rng(7).integers(lo, hi + 1, 256).astype(float)
    net, got = harness.builder(config).build(config, x)
    repro.compile(net, backend="host").run()
    want = harness.reference(config).reference(config, x)
    for port in config["egress"]:
        # the host interpreter runs the 8-point transform in float32
        np.testing.assert_allclose(np.asarray(got[port]), want[port],
                                   rtol=1e-6, atol=1e-5)
        assert len(got[port]) == len(x)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_control_fails_the_cell(name):
    """The reference one precision step lower, over seeded inputs of one
    clip or chunk run, reads above the cell's limit on some number."""
    config = _config(name)
    lo, hi = config["value_range"]
    ref = harness.reference(config)
    worst = {}
    for seed in (11, 12, 13):
        x = np.random.default_rng(seed).integers(lo, hi + 1, 48_000)
        x = x.astype(float)
        want, ctl = ref.reference(config, x), ref.control(config, x)
        for port in config["egress"]:
            gap = float(np.max(np.abs(ctl[port] - want[port])))
            worst[port] = min(worst.get(port, np.inf), gap)
    limits = config["checks"]
    assert any(worst[p] > limits[f"max_gap.{p}"] for p in config["egress"])


def test_bf16_rounding_and_three_pass_matmul():
    from bench.reference import bf16, matmul_bf16_3x

    assert bf16(np.float32(1.0 + 2 ** -9)) == np.float32(1.0)   # tie to even
    assert bf16(np.float32(1.0 + 3 * 2 ** -9)) == np.float32(1.0 + 2 ** -7)
    rng = np.random.default_rng(0)
    a, b = rng.normal(size=(64, 8)), rng.normal(size=(8, 8))
    exact = a @ b
    three = matmul_bf16_3x(a, b)
    one = bf16(a) @ bf16(b)
    assert np.abs(three - exact).max() < np.abs(one - exact).max() / 50
    assert np.abs(three - exact).max() > 1e-7
