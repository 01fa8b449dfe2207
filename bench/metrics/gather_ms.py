"""Device time per step of the ops the program tags ``fs.transpose_vals``
(``ADPlan.transpose_vals``: the gather that re-lays the adjacency's
values for each transposed SpMM, and its reshapes and mask multiply),
read from the tags on the window's ops (``bench/program_trace.py``)."""

from bench import program_trace


def read(ctx):
    m = program_trace.marks(ctx)
    if m is None or ctx.steps == 0:
        return None
    ops = m.tagged(program_trace.GATHER)
    if not ops and any(o.meta.get("dir") == "bwd" for o in m.spmm()):
        raise ValueError(f"transposed SpMM launches ran, but no op in the "
                         f"window carries the tag {program_trace.GATHER!r}")
    return sum(o.dur for o in ops) / ctx.steps / 1e6
