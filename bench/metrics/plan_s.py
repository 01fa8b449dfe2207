"""Host time of the program's differentiable-op plan
(``core/autodiff.ad_plan``: transpose, blocking, value permutation), from
the benchmark's span around the call."""


def read(ctx):
    return ctx.spans.get("plan")
