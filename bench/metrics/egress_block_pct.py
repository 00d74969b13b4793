"""Share of the window's delivered tokens that reached a session's result
buffer as array blocks, with no per-token Python object on the way:
100 x delta ``tokens_delivered_blocks`` / delta ``tokens_delivered`` between
the telemetry snapshots at the window's two ends.  A program without the
counter reads nothing."""


def read(ctx):
    blocks0 = getattr(ctx.tel0, "tokens_delivered_blocks", None)
    blocks1 = getattr(ctx.tel1, "tokens_delivered_blocks", None)
    delivered = ctx.tel1.tokens_delivered - ctx.tel0.tokens_delivered
    if blocks0 is None or blocks1 is None or delivered <= 0:
        return None
    return 100.0 * (blocks1 - blocks0) / delivered
