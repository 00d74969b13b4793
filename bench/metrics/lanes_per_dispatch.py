"""Session lanes per batched device launch over the window, from the
server's exact telemetry counters (device_lanes / device_dispatches)."""


def read(ctx):
    dispatches = ctx.tel1.device_dispatches - ctx.tel0.device_dispatches
    if dispatches <= 0:
        return None
    return (ctx.tel1.device_lanes - ctx.tel0.device_lanes) / dispatches
