"""Host milliseconds per step spent in ``WindowLoop.batches`` (taking the
window's batches from the program's prefetching pipeline, stacking and
placing them) during the traced window: the time a window waits for its
input before it can be dispatched."""
from bench import tracing


def read(ctx):
    secs = tracing.host_span_seconds(ctx["trace"], "bench.batches")
    return 1e3 * secs / ctx["steps"]
