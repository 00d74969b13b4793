"""The stateful fused stream kernel's share of its roofline over the window.

Kernel time is the device time of the trace's calls of the configuration's
kernel.  Each call's tokens and session lanes come from its result's shape
(lanes x output wires x rows x row width, pad lanes and padded rows
included).  The least time the chip could take is the larger of the
algorithm's bytes over peak HBM bandwidth and its operations over peak
FLOP/s (``bench/work/stateful_fused.py``).  A configuration without state
registers reads nothing."""

import math

from bench.work.stateful_fused import call_work


def read(ctx):
    r, kernel = ctx.trace, ctx.config["kernel"]
    if r is None or ctx.peaks is None or not kernel.get("state_registers"):
        return None
    calls = r.calls_of(kernel["name"])
    seconds = sum(c.end - c.start for c in calls) / 1e9
    out_wires = kernel["out_wires"]
    tokens = sum(math.prod(c.shape) for c in calls) // out_wires
    lanes = sum(math.prod(c.shape) // (out_wires * math.prod(c.shape[-2:]))
                for c in calls if len(c.shape) >= 3)
    if seconds <= 0 or tokens <= 0:
        return None
    ops, nbytes = call_work(kernel["ops"], kernel["in_wires"], out_wires,
                            tokens, lanes, kernel["state_registers"])
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    t_ops = ops / ctx.peaks["flops_per_s"]
    ctx.log(f"{kernel['name']} (stateful) roofline: {tokens} tokens of "
            f"{lanes} lanes in {len(calls)} calls, {seconds!r} s of kernel "
            f"time; bound by {'bytes' if t_bytes >= t_ops else 'operations'} "
            f"({t_bytes!r} s for the bytes, {t_ops!r} s for the operations)")
    return 100.0 * max(t_bytes, t_ops) / seconds
