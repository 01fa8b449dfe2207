"""Device time of the SpMM kernels over the DMAs their launches start
(each launch's ``dmas`` in its ``kernel_metadata``, counted from its
shapes by ``kernels/spmm_pallas.spmm_launch_counts``), in ns per DMA."""

from bench import program_trace


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None:
        return None
    return (sum(o.dur for o in m.spmm())
            / program_trace.launch_sum(m, "dmas"))
