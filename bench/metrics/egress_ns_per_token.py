"""Host nanoseconds per token of session egress: result FIFOs to each session's
results.  Seconds of the program's ``repro.engine.egress`` spans in the
traced window over the tokens that ``ServerTelemetry.tokens_delivered``
counted between the telemetry snapshots at the window's two ends."""

from bench import spans


def read(ctx):
    return spans.ns_per_token(ctx, "repro.engine.egress", "tokens_delivered")
