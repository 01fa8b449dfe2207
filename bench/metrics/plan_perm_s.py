"""Host time of the value permutation ``ad_plan`` builds
(``core/autodiff._blocked_perm``), from the program's own span
``fs.ad_plan.perm`` (``repro.core.metrics.spans``): its last call, the
one set-up made."""


def read(ctx):
    from repro.core import metrics

    spans = getattr(metrics, "spans", None)
    rec = spans().get("fs.ad_plan.perm") if spans else None
    return rec["last_s"] if rec else None
