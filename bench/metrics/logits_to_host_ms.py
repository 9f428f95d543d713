"""Median over the engine steps in the window of the time the step spent
copying logits to the host: the summed ``serve.*.to_host`` spans inside
each ``serve.step`` (the slab's and the decode step's), traced run."""
from bench import program_spans


def read(run):
    return program_spans.median_per_step_ms(run, "to_host")
