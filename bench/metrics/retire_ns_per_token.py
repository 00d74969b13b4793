"""Host nanoseconds per token of the batcher's retire: device outputs to numpy,
the mask select and the host FIFOs.  Seconds of the program's
``repro.batcher.retire`` spans in the traced window over the tokens that
``ServerTelemetry.device_tokens_out`` counted between the telemetry
snapshots at the window's two ends."""

from bench import spans


def read(ctx):
    return spans.ns_per_token(ctx, "repro.batcher.retire", "device_tokens_out")
