"""Bytes the window's SpMM launches move by DMA (``dma_bytes`` in their
``kernel_metadata``) over the least bytes of the step's aggregations
(``counts["spmm"]["bytes"]``, ``bench/models``) times the steps."""

from bench import program_trace


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None or ctx.steps == 0:
        return None
    return (program_trace.launch_sum(m, "dma_bytes")
            / (ctx.counts["spmm"]["bytes"] * ctx.steps))
