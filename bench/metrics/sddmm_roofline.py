"""SDDMM kernels' share of their roofline: the least time of the SDDMMs
the step needs (the backward's ``dP``, counted from the graph and the
widths in ``bench/models``) over the device time of every SDDMM kernel
in the window, the recomputed scores' included, so the recompute shows
as a lower share."""

from bench import trace

KERNEL = "_sddmm_call"    # the Pallas launcher's name in the trace


def read(ctx):
    return trace.roofline_share(ctx, "sddmm", KERNEL)
