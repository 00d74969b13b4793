"""Shared test fixtures: the paper's TopFilter network and friends.

Also provides an optional-``hypothesis`` shim: modules that mix example-based
and property-based tests import ``given``/``settings``/``st`` from here, so a
missing ``hypothesis`` degrades the property tests to skips instead of failing
the whole module at collection (install via requirements-dev.txt).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import pytest

from repro.core.actor import Actor, Action, Port, simple_actor, sink_actor, source_actor
from repro.core.graph import ActorGraph

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # degrade property tests to skips
    HAVE_HYPOTHESIS = False

    class _Anything:
        """Stands in for ``hypothesis.strategies`` so strategy expressions at
        decoration time (``st.lists(st.integers(0, 9)).map(...)``) evaluate."""

        def __getattr__(self, name):
            return self

        def __call__(self, *args, **kwargs):
            return self

    st = _Anything()

    def given(*_args, **_kwargs):
        return pytest.mark.skip(
            reason="hypothesis not installed (pip install -r requirements-dev.txt)"
        )

    def settings(*_args, **_kwargs):
        return lambda fn: fn


def abstract_mesh(axis_sizes, axis_names):
    """A device-free ``jax.sharding.AbstractMesh`` for sharding-rule tests."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def lcg_values(n: int, mod: int = 100) -> List[int]:
    return [(x * 1103515245 + 12345) % mod for x in range(n)]


def make_topfilter(
    param: int = 50, n: int = 1024, *, vectorized: bool = False
) -> Tuple[ActorGraph, List]:
    """The paper's Listing-1 network: Source -> Filter (guard + priority) -> Sink."""
    g = ActorGraph("TopFilter")

    def gen(st):
        x = st.get("x", 0)
        return {**st, "x": x + 1}, float((x * 1103515245 + 12345) % 100)

    g.add(source_actor("source", gen, dtype="float32",
                       has_next=lambda st: st.get("x", 0) < n))

    def pred(st, peeked):
        return peeked["IN"][0] < param

    def vf(state, ins):
        vals, mask = ins["IN"]
        return state, {"OUT": (vals, mask & (vals < param))}

    g.add(
        Actor(
            "filter",
            inputs=[Port("IN", "float32")],
            outputs=[Port("OUT", "float32")],
            actions=[
                Action("t0", consumes={"IN": 1}, produces={"OUT": 1},
                       guard=pred, fire=lambda st, t: (st, {"OUT": [t["IN"][0]]})),
                Action("t1", consumes={"IN": 1}, fire=lambda st, t: (st, {})),
            ],
            vector_fire=vf if vectorized else None,
        )
    )
    got: List = []
    g.add(sink_actor("sink", lambda st, v: (got.append(float(v)), st)[1],
                     dtype="float32"))
    g.connect("source", "filter")
    g.connect("filter", "sink")
    return g, got


def topfilter_expected(param: int = 50, n: int = 1024) -> List[float]:
    return [float(v) for v in lcg_values(n) if v < param]


def drain_source(graph, name="source"):
    """The exact token stream the network's source would generate — what a
    serve-mode client submits in its place."""
    actor = graph.actors[name]
    action = actor.actions[0]
    state = dict(actor.initial_state)
    out = []
    while action.guard is None or action.guard(state, {}):
        state, produced = action.fire(state, {})
        vals = produced.get(actor.outputs[0].name, [])
        if not vals:
            break
        out.extend(vals)
    return out


def make_chain(n_stages: int = 4, n_tok: int = 256) -> Tuple[ActorGraph, List]:
    g = ActorGraph("chain")

    def gen(st):
        x = st.get("i", 0)
        return {"i": x + 1}, float(x)

    g.add(source_actor("src", gen, has_next=lambda st: st.get("i", 0) < n_tok))
    prev = "src"
    for i in range(n_stages):
        g.add(simple_actor(f"s{i}", lambda st, v, k=i: (st, v + k + 1)))
        g.connect(prev, f"s{i}")
        prev = f"s{i}"
    got: List = []
    g.add(sink_actor("snk", lambda st, v: (got.append(float(v)), st)[1]))
    g.connect(prev, "snk")
    return g, got
