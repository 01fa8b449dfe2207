"""Device time per step of the XLA operations outside the Pallas kernels
(the value re-layout gather, dense matmuls, elementwise work)."""

from bench import trace


def read(ctx):
    if not ctx.ops or ctx.steps == 0:
        return None
    xla = [e for e in ctx.ops if not trace.is_kernel(e)]
    return sum(e.dur for e in xla) / ctx.steps / 1e6
