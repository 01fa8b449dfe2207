"""Host time of the first call of the program's train step (trace and
compile, or a compile-cache read, and one step), from the benchmark's span
around the call and its wait."""


def read(ctx):
    return ctx.spans.get("first_step")
