"""Share of the window's pumped tokens that moved from a session's
admission queue into its ingress FIFO as array blocks, with no per-token
Python object on the way: 100 x delta ``tokens_pumped_blocks`` / delta
``tokens_pumped`` between the telemetry snapshots at the window's two ends.
A program without the counter reads nothing."""


def read(ctx):
    blocks0 = getattr(ctx.tel0, "tokens_pumped_blocks", None)
    blocks1 = getattr(ctx.tel1, "tokens_pumped_blocks", None)
    if blocks0 is None or blocks1 is None:
        return None
    pumped = ctx.tel1.tokens_pumped - ctx.tel0.tokens_pumped
    if pumped <= 0:
        return None
    return 100.0 * (blocks1 - blocks0) / pumped
