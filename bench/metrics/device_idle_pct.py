"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in percent."""
from bench import tracing


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - tracing.busy_s(trace) / tracing.window_s(trace))
