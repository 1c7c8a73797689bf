"""The HBM roofline share of the ``pool_pack`` kernel, in percent: the
bytes it needs per step (``bench.counts.pool_kernel_bytes``) at the
chip's published HBM bandwidth, over its device time per step."""
from bench import tracing


def read(ctx):
    return tracing.roofline_pct(ctx, ["pool_pack"])
