"""FIR29 low-pass: source -> seed -> 29 delay taps -> sink (acc) and xsink
(x delayed by 29 samples), the CAL systolic FIR of the configuration's
``taps``."""

from __future__ import annotations

from bench.networks import replay_source


def build(config, values=()):
    from repro.apps.streams import FirSeed, FirTap
    from repro.frontend import network

    net = network(config["network"])
    src = replay_source(net, values)
    seed = net.add(FirSeed, "seed")
    src.OUT >> seed.IN
    prev = seed
    for i, c in enumerate(config["taps"]):
        tap = net.add(FirTap(float(c)), f"tap{i}")
        prev.XOUT >> tap.XIN
        prev.AOUT >> tap.AIN
        prev = tap
    got = {"sink": [], "xsink": []}
    snk = net.sink("sink", collect=got["sink"])
    xsink = net.sink("xsink", collect=got["xsink"])
    prev.AOUT >> snk.IN
    prev.XOUT >> xsink.IN
    return net, got
