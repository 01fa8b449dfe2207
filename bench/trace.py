"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-op device time and idle gaps under the benchmark's host spans.

Device operations are the events of each TPU plane's ``XLA Ops`` line;
the benchmark's host spans are its ``bench.*`` ``TraceAnnotation`` events
on the host plane, on the same clock.  Every number is in nanoseconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_INSTANCE = re.compile(r"\.\d+$")
_HLO_TEXT = re.compile(r"^%?([^\s=]+) = ")
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    kernel: bool = False       # a Pallas kernel (a TPU custom call)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict        # device id -> [Event], sorted by start
    spans: list      # [Event] of the benchmark's host spans

    def span(self, name):
        """The first host span called ``name``."""
        for s in self.spans:
            if s.name == name:
                return s
        raise KeyError(f"no host span {name!r} in the trace")


def find_xplane(log_dir) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    device_op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m:
                spans.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in ops.values():
        evs.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops=ops, spans=spans)


def device_op(text: str, start: float, end: float) -> Event:
    """A device op from its trace event, whose name is the HLO
    instruction's text (``%fusion.3 = f32[8]{0} fusion(...), ...``): the
    op's own name, and whether it is a Pallas kernel.  Operands' names
    stay out of the name, so an op that reads a kernel's output is not
    taken for the kernel."""
    m = _HLO_TEXT.match(text)
    name = m.group(1) if m else text
    return Event(name, start, end, kernel=_PALLAS_TARGET in text)


def window(trace, span="bench.window"):
    """``(start, end)`` of the traced window: the host span.  The devices'
    ops have to lie on its clock, most of their time inside it; where
    they do not, host spans cannot bound the window or name its gaps, and
    that is an error."""
    s = trace.span(span)
    ops = [e for evs in trace.ops.values() for e in evs]
    if not ops:
        raise ValueError("the trace holds no device op")
    total = sum(e.dur for e in ops)
    inside = sum(e.dur for e in clip(ops, s.start, s.end))
    if inside < 0.5 * total:
        raise ValueError(f"device ops and the host span {span!r} are not on "
                         f"one clock: {inside / total:.1%} of the ops' time "
                         f"lies inside it")
    return s.start, s.end


def clip(events, lo, hi):
    """The parts of ``events`` that lie inside ``[lo, hi]``."""
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events):
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [tuple(iv) for iv in merged]


def busy(events, lo, hi) -> float:
    """Time inside ``[lo, hi]`` in which some event runs."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def op_name(name: str) -> str:
    """An op's name without its instance number (``fusion.12`` ->
    ``fusion``)."""
    return _INSTANCE.sub("", name)


def per_op(events) -> dict:
    """Device time per op name, instance numbers merged."""
    out = {}
    for e in events:
        key = op_name(e.name)
        out[key] = out.get(key, 0.0) + e.dur
    return out


def idle_gaps(events, lo, hi):
    """``(start, end)`` of the stretches of ``[lo, hi]`` with no event."""
    gaps, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans, t) -> str:
    """The name of the shortest host span that holds time ``t``
    (``"outside"`` when none does)."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best else "outside"


def is_kernel(event) -> bool:
    """A Pallas kernel: a TPU custom call (named in the trace after the
    jitted launcher that wraps its ``pallas_call``)."""
    return event.kernel


def roofline_share(ctx, family: str, kernel: str):
    """Percent of ``family``'s roofline reached by the device ops whose
    name holds ``kernel``; ``None`` where the model does no such work.
    Work with no op of that name in the window is an error: the kernel
    was renamed, and its time would be counted as XLA's."""
    work = ctx.counts.get(family)
    if work is None:
        return None
    spent = sum(e.dur for e in ctx.ops
                if e.kernel and kernel in e.name) * 1e-9
    if spent <= 0 or ctx.steps == 0:
        raise ValueError(f"the step does {family} work, but no device op in "
                         f"the window is named after {kernel!r}")
    least = max(work["ops"] / ctx.peak.flops,
                work["bytes"] / ctx.peak.hbm_bytes) * ctx.steps
    return 100.0 * least / spent
