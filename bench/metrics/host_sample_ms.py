"""Median over the engine steps in the window of the host's sampling time:
the summed ``serve.*.sample`` spans inside each ``serve.step`` (sampling,
token bookkeeping, prefix publishing), traced run."""
from bench import program_spans


def read(run):
    return program_spans.median_per_step_ms(run, "sample")
