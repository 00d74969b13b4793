"""Host nanoseconds per token of the admission pump: admission queues to
ingress FIFOs, every session.  Seconds of the program's
``repro.engine.pump`` spans in the traced window over the tokens that
``ServerTelemetry.tokens_pumped`` counted between the telemetry snapshots at
the window's two ends."""

from bench import spans


def read(ctx):
    return spans.ns_per_token(ctx, "repro.engine.pump", "tokens_pumped")
