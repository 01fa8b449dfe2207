"""The whole step's share of the chip's peak: the model's FLOPs per step
(forward and backward, nothing recomputed; ``bench/models``) times the
traced window's steps, over the window's length and the bf16 peak."""


def read(ctx):
    lo, hi = ctx.window
    if not ctx.ops or ctx.steps == 0 or hi <= lo:
        return None
    flops = ctx.counts["step_flops"] * ctx.steps
    return 100.0 * flops / ((hi - lo) * 1e-9 * ctx.peak.flops)
