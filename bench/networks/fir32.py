"""FIR32: source -> seed -> 32 MAC taps -> sink (acc) and xsink (x)."""

from __future__ import annotations

from bench.networks import replay_source


def build(config, values=()):
    from repro.apps.streams import FirSeed, Mac
    from repro.frontend import network

    net = network(config["network"])
    src = replay_source(net, values)
    seed = net.add(FirSeed, "seed")
    src.OUT >> seed.IN
    prev = seed
    for i, c in enumerate(config["taps"]):
        mac = net.add(Mac(float(c)), f"mac{i}")
        prev.XOUT >> mac.XIN
        prev.AOUT >> mac.AIN
        prev = mac
    got = {"sink": [], "xsink": []}
    snk = net.sink("sink", collect=got["sink"])
    xsink = net.sink("xsink", collect=got["xsink"])
    prev.AOUT >> snk.IN
    prev.XOUT >> xsink.IN
    return net, got
