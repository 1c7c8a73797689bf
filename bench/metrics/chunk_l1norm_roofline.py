"""The HBM roofline share of the ``chunk_l1norm`` kernel, in percent: the
bytes it needs per step (``bench.counts.pool_kernel_bytes``) at the
chip's published HBM bandwidth, over its device time per step."""
from bench import tracing


def read(ctx):
    return tracing.roofline_pct(ctx, ["chunk_l1norm"])
