"""Device time of the fused attention kernels over the DMAs their launches
start (each launch's ``dmas`` in its ``kernel_metadata``, counted from its
shapes by ``kernels/attention_pallas.attention_launch_counts``), in ns per
DMA.  A program whose attention launches carry no metadata reads
nothing."""

from bench import program_trace

KERNEL = "_attn_call"     # the Pallas launcher's name in the trace


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None:
        return None
    kernels = [o for o in m.ops if o.kernel and KERNEL in o.name]
    bare = [o.name for o in kernels if not o.meta]
    if not kernels or len(bare) == len(kernels):
        return None
    if bare:
        raise ValueError(f"of the window's {len(kernels)} kernels named "
                         f"after {KERNEL!r}, {len(bare)} carry no "
                         f"kernel_metadata ({bare[:3]})")
    return (sum(o.dur for o in kernels)
            / sum(int(o.meta["dmas"]) for o in kernels))
