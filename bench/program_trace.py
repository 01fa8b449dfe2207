"""The program's own marks on a traced window: the ``fs.*`` host spans
(``repro.core.metrics.span``), the ``flashsparse_op`` tags on XLA ops
(``metrics.op_tag``) and the ``kernel_metadata`` each SpMM launch carries
(op, direction, grid steps, DMAs, DMA bytes, MXU multiply-adds).

A v5e trace names each device op by its HLO text, frontend attributes
included, so tags and kernel metadata are read from the op's text.  The
window's ``.xplane.pb`` is found where the harness writes it
(``harness.WORK_DIR / "trace" / <cell>``).  A program without spans, as
before it had them, yields nothing (``marks`` returns ``None``); a
program with them whose SpMM kernels lack metadata is an error, as in
``trace.roofline_share``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import re
from typing import Optional

from bench import harness, trace

TAG = re.compile(r'flashsparse_op="([^"]*)"')
META = re.compile(r"kernel_metadata=(\{[^{}]*\})")
SPMM = "_spmm_call"          # the SpMM launcher's name in the trace
GATHER = "fs.transpose_vals"
STEP = "fs.step"
SPAN_PREFIX = "fs."


@dataclasses.dataclass(frozen=True)
class Op:
    """A device op inside the window, with the program's marks."""

    name: str
    start: float
    end: float
    kernel: bool
    tag: Optional[str]
    meta: dict               # kernel_metadata; empty where there is none

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Marks:
    ops: list                # device 0's ops, clipped to the window
    spans: list              # trace.Event of the fs.* host spans inside it

    def spmm(self):
        return [o for o in self.ops if o.kernel and SPMM in o.name]

    def tagged(self, tag):
        return [o for o in self.ops if o.tag == tag]


def kernel_metadata(text: str) -> dict:
    """The ``kernel_metadata`` JSON of an op's HLO text (a flat object,
    which XLA prints bare); ``{}`` where it has none."""
    m = META.search(text)
    return json.loads(m.group(1)) if m else {}


@functools.lru_cache(maxsize=2)
def load(path, lo: float, hi: float) -> Marks:
    """Device 0's ops and the ``fs.*`` host spans of ``path`` inside
    ``[lo, hi]`` (ns), read once for the window's readers."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) == 0 and line.name == trace.OPS_LINE:
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if b <= lo or a >= hi:
                        continue
                    ev = trace.device_op(e.name, a, b)
                    tag = TAG.search(e.name)
                    ops.append(Op(ev.name, max(a, lo), min(b, hi), ev.kernel,
                                  tag.group(1) if tag else None,
                                  kernel_metadata(e.name)))
            elif not m:
                spans.extend(
                    trace.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                    and e.start_ns >= lo
                    and e.start_ns + e.duration_ns <= hi)
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda e: e.start)
    return Marks(ops=ops, spans=spans)


def xplane_of(ctx) -> str:
    return trace.find_xplane(harness.WORK_DIR / "trace" / ctx.cell.name)


def marks(ctx) -> Optional[Marks]:
    """The window's marks, or ``None`` where the program put no span in
    it.  Raises where the step does SpMM work and a kernel in the window
    carries no metadata, or none is named after the launcher."""
    m = load(xplane_of(ctx), *ctx.window)
    if not m.spans:
        return None
    if ctx.counts.get("spmm") is not None:
        kernels = m.spmm()
        bare = [o.name for o in kernels if not o.meta]
        if not kernels or bare:
            raise ValueError(
                f"the step does SpMM work, but of the window's "
                f"{len(kernels)} kernels named after {SPMM!r}, "
                f"{len(bare)} carry no kernel_metadata ({bare[:3]})")
    return m


def launch_sum(m: Marks, key: str) -> int:
    """A ``kernel_metadata`` count summed over the window's SpMM
    launches."""
    return sum(int(o.meta[key]) for o in m.spmm())
