"""The paper's computation redundancy as the TPU does it: multiply-adds
the window's SpMM launches run on the MXU (``mxu_macs`` in their
``kernel_metadata``: every K-block's full (V, k_blk) x (k_blk, n_blk)
tile) over the multiply-adds the step's aggregations need
(``counts["spmm"]["ops"] / 2``, ``bench/models``) times the steps."""

from bench import program_trace


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None or ctx.steps == 0:
        return None
    return (program_trace.launch_sum(m, "mxu_macs")
            / (ctx.counts["spmm"]["ops"] / 2 * ctx.steps))
