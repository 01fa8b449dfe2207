"""Share of the traced window in which no operation runs on the device:
1 - (union of the device's op intervals) / window."""

from bench import trace


def read(ctx):
    lo, hi = ctx.window
    if not ctx.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - trace.busy(ctx.ops, lo, hi) / (hi - lo))
