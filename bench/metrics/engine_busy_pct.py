"""Share of the traced window in which the serving engine's thread was at
work: the union of its ``repro.engine.*`` round phases and the batcher's
``repro.batcher.*`` spans, the park excepted, over the window.

It also logs each program span's seconds and count in the window, the
share of the device's idle time that lies under a program span, and the
ten longest idle gaps labelled by the program span over each."""

from bench import spans as program_spans


def read(ctx):
    spans = program_spans.read(ctx)
    if spans is None or spans.window_s <= 0:
        return None
    busy = spans.union_s(program_spans.engine_busy)
    if busy <= 0:
        return None
    r = ctx.trace
    for name in spans.names():
        ctx.log(f"program span {name}: {spans.span_s(name)!r} s in "
                f"{spans.count(name)} events")
    idle_s = sum(e - s for s, e in r.idle) / 1e9
    if idle_s > 0:
        ctx.log(f"device idle time under a program span: "
                f"{100.0 * spans.covered_s(r.idle) / idle_s!r} % of "
                f"{idle_s!r} s")
    longest = sorted(r.idle, key=lambda g: g[0] - g[1])[:10]
    ctx.log("longest idle gaps by program span: " + repr(
        [[spans.label(g, r.notes), (g[1] - g[0]) / 1e9] for g in longest]))
    return 100.0 * busy / spans.window_s
