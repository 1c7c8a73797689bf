"""Host milliseconds per step in which the training loop waited on the
program's prefetch queue for a batch: its ``repro.data.get`` spans in the
traced window (``bench.scopes``)."""
from bench import scopes


def read(ctx):
    return scopes.span_ms(ctx, "repro.data.get")
