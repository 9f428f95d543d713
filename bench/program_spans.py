"""The program's own host spans in a traced run (the serving engine's
``serve.*`` spans, on the profiler's clock): the spans of one name inside
the measured window, and per engine step the summed time of one kind of
child span.  A program without these spans gives nothing to read."""
from __future__ import annotations

import statistics

from bench import trace

STEP = "serve.step"


def in_window(run, name: str) -> list:
    """(start, end) in ns of every host span ``name`` wholly inside the
    window (``bench.window``); empty without a trace."""
    table = run.trace_table
    if table is None:
        return []
    w0, w1 = trace.window_on_profile_clock(table, "bench.window")
    return [(s, s + d) for n, _, s, d in table["host"]
            if n == name and w0 <= s and s + d <= w1]


def median_per_step_ms(run, child: str) -> float | None:
    """Median over the engine steps (``serve.step``) in the window of the
    summed time of the spans ``serve.<phase>.<child>`` inside each step, in
    ms; a step with none counts 0.  None without a trace or a step span."""
    steps = in_window(run, STEP)
    if not steps:
        return None
    suffix = "." + child
    kids = sorted((s, s + d) for n, _, s, d in run.trace_table["host"]
                  if n.startswith("serve.") and n.endswith(suffix))
    per_step = []
    for a, b in steps:
        per_step.append(sum(e - s for s, e in kids if a <= s and e <= b))
    return statistics.median(per_step) / 1e6
