"""IDCT8: source -> descale -> 8-point IDCT -> clip -> sink."""

from __future__ import annotations

from bench.networks import replay_source


def _descale_vf(state, ins):
    vals, mask = ins["IN"]
    return state, {"OUT": ((vals - 128.0) / 8.0, mask)}


def _clip_vf(state, ins):
    import jax.numpy as jnp

    vals, mask = ins["IN"]
    return state, {"OUT": (jnp.clip(vals, -256.0, 255.0), mask)}


def build(config, values=()):
    from repro.apps.streams import Idct
    from repro.frontend import network

    net = network(config["network"])
    src = replay_source(net, values)
    descale = net.map("descale", lambda st, v: (st, (v - 128.0) / 8.0),
                      vector_fire=_descale_vf,
                      stream_op=("affine", -128.0, 0.125, 0.0))
    idct = net.add(Idct, "idct")
    clip = net.map("clip", lambda st, v: (st, max(-256.0, min(255.0, v))),
                   vector_fire=_clip_vf, stream_op=("clip", -256.0, 255.0))
    got = {"sink": []}
    snk = net.sink("sink", collect=got["sink"])
    src >> descale >> idct >> clip >> snk
    return net, got
