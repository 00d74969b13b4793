"""Host nanoseconds per token of the batcher's launch: each lane's staging,
lane packing, state stacking and the jitted call's enqueue.  Seconds of the
program's ``repro.batcher.launch`` spans in the traced window over the
tokens that ``ServerTelemetry.device_tokens_in`` counted between the
telemetry snapshots at the window's two ends."""

from bench import spans


def read(ctx):
    return spans.ns_per_token(ctx, "repro.batcher.launch", "device_tokens_in")
