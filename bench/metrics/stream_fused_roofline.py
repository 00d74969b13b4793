"""The fused stream kernel's share of its roofline over the window.

Kernel time is the device time of the trace's ``stream_fused`` calls.  Each
call's tokens come from its result's shape (output wires x tokens, pad lanes
and padded rows included).  The least time the chip could take is the
larger of the algorithm's bytes over peak HBM bandwidth and its operations
over peak FLOP/s (``bench/work/stream_fused.py``)."""

import math

from bench.work.stream_fused import call_work


def read(ctx):
    r, kernel = ctx.trace, ctx.config["kernel"]
    if r is None or ctx.peaks is None:
        return None
    calls = r.calls_of(kernel["name"])
    seconds = sum(c.end - c.start for c in calls) / 1e9
    tokens = sum(math.prod(c.shape) for c in calls) // kernel["out_wires"]
    if seconds <= 0 or tokens <= 0:
        return None
    ops, nbytes = call_work(kernel["ops"], kernel["in_wires"],
                            kernel["out_wires"], tokens)
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    t_ops = ops / ctx.peaks["flops_per_s"]
    ctx.log(f"{kernel['name']} roofline: {tokens} tokens in {len(calls)} "
            f"calls, {seconds!r} s of kernel time; bound by "
            f"{'bytes' if t_bytes >= t_ops else 'operations'} ({t_bytes!r} s "
            f"for the bytes, {t_ops!r} s for the operations)")
    return 100.0 * max(t_bytes, t_ops) / seconds
