"""Blocks each session lane ran per batched device launch over the window:
delta ``device_blocks`` / delta ``device_lanes`` between the telemetry
snapshots at the window's two ends, the megastep depth in effect.  A
program without the counter reads nothing."""


def read(ctx):
    blocks0 = getattr(ctx.tel0, "device_blocks", None)
    blocks1 = getattr(ctx.tel1, "device_blocks", None)
    lanes = ctx.tel1.device_lanes - ctx.tel0.device_lanes
    if blocks0 is None or blocks1 is None or lanes <= 0:
        return None
    return (blocks1 - blocks0) / lanes
