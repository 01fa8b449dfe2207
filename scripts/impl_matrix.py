"""Generate the README implementation matrix from the dispatch registry.

  PYTHONPATH=src python scripts/impl_matrix.py

Prints a GitHub-markdown table of every registered (op, impl) pair with
its capability flags, pulled live from :mod:`repro.core.dispatch` — the
single source of truth every layer resolves implementations through.
``scripts/check_docs.py`` regenerates this table in CI and fails if the
committed README copy has drifted.
"""

from __future__ import annotations

OPS = ("spmm", "sddmm", "attention")
FLAGS = (
    ("differentiable", "grad"),
    ("batched", "batched"),
    ("load_balanced", "balanced"),
    ("multi_device", "multi-dev"),
    ("overlapped", "overlap"),
)


def _fallback_cell(name: str, entries) -> str:
    """Next ladder rung per op (DESIGN.md §15).  One shared rung renders
    bare; per-op differences render ``op:rung``; terminal/no-fallback
    impls render a dash."""
    from repro.core import dispatch

    fbs = {op: dispatch.fallback_for(op, name) for op in entries}
    uniq = {fb for fb in fbs.values() if fb is not None}
    if not uniq:
        return "—"
    if len(uniq) == 1 and all(fb is not None for fb in fbs.values()):
        return f"`{uniq.pop()}`"
    return " ".join(f"{op}:`{fb}`" if fb else f"{op}:—"
                    for op, fb in fbs.items())


def _precision_cell(entries) -> str:
    """Union of precision levels over the ops an impl serves, in canonical
    order (DESIGN.md §13) — fp32-only renders as a dash (the default)."""
    levels = {p for e in entries.values() for p in e.precisions}
    if levels <= {"fp32"}:
        return "—"
    return "/".join(p for p in ("fp32", "bf16", "int8") if p in levels)


def impl_matrix() -> str:
    """The implementation matrix as a GitHub-markdown table string."""
    from repro.core import dispatch

    names = sorted({n for op in OPS for n in dispatch.impls(op)})
    header = (["impl"] + [f"{op}" for op in OPS]
              + [lbl for _, lbl in FLAGS] + ["precision", "fallback"])
    lines = ["| " + " | ".join(header) + " |",
             "|" + "|".join("---" for _ in header) + "|"]
    for name in names:
        entries = {op: dispatch.get(op, name) for op in OPS
                   if name in dispatch.impls(op)}
        row = [f"`{name}`"]
        row += ["✓" if op in entries else "—" for op in OPS]
        for flag, _ in FLAGS:
            vals = {getattr(e, flag) for e in entries.values()}
            row.append("✓" if vals == {True} else
                       ("—" if vals == {False} else "mixed"))
        row.append(_precision_cell(entries))
        row.append(_fallback_cell(name, entries))
        lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(impl_matrix())
