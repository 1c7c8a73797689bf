"""Device milliseconds per step of all Mosaic (Pallas) kernels in the
traced window, averaged over the chips: GradientFlow's pack, update and
CSC kernels (``bench.tracing.mosaic_seconds``)."""
from bench import tracing


def read(ctx):
    total = sum(tracing.mosaic_seconds(ctx["trace"], ctx["steps"]).values())
    return 1e3 * total if total > 0 else None
