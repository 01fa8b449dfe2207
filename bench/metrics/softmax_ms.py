"""Device time per step of the ops the program tags ``fs.sparse_softmax``
(``core/softmax.sparse_softmax`` in the attention backward's recompute,
and the ops of its own backward), read from the tags on the window's ops
(``bench/program_trace.py``); nothing where the program marks no
attention launch, an error where it does and no op carries the tag."""

from bench import program_trace

TAG = "fs.sparse_softmax"
KERNEL = "_attn_call"     # the fused forward, whose backward is tagged


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None or ctx.steps == 0:
        return None
    ops = m.tagged(TAG)
    if not ops:
        if any(o.kernel and KERNEL in o.name and o.meta for o in m.ops):
            raise ValueError(f"attention launches carry kernel_metadata, but "
                             f"no op in the window carries the tag {TAG!r}")
        return None
    return sum(o.dur for o in ops) / ctx.steps / 1e6
