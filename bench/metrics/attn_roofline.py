"""Fused attention kernels' share of their roofline: the least time of the
step's attention forwards (the larger of their operations over the peak
FLOP/s and their bytes over the peak HBM bandwidth, counted from the graph
and the widths in ``bench/models``) over the attention kernels' device
time."""

from bench import trace

KERNEL = "_attn_call"     # the Pallas launcher's name in the trace


def read(ctx):
    return trace.roofline_share(ctx, "attention", KERNEL)
