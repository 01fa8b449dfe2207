"""Median host time of one call of the program's train step in the
traced window: the ``fs.step`` spans (``train/train_step.py``), which
last the dispatch of a step, not its device work."""

import statistics

from bench import program_trace


def read(ctx):
    m = program_trace.marks(ctx)
    steps = [s.dur for s in m.spans if s.name == program_trace.STEP] if m \
        else []
    return statistics.median(steps) / 1e6 if steps else None
