"""Host time of the program's format build (``core/format.from_coo``),
from the benchmark's span around the call."""


def read(ctx):
    return ctx.spans.get("format")
