"""The pool kernels' share of their HBM roofline, in percent: the bytes
they need per step (``bench.counts.pool_kernel_bytes``, padding left
out) at the chip's published HBM bandwidth, over their measured device
time per step. Nothing is read where the trace lacks a kernel the count
covers."""
from bench import tracing


def read(ctx):
    return tracing.roofline_pct(ctx, sorted(ctx["kernel_bytes"]))
