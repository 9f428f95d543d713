"""Median time of one chunked-prefill slab in the window: the engine's
``serve.chunk`` span (the slab's inputs, dispatch, device time, logits to
the host, first tokens sampled), from the traced run."""
import statistics

from bench import program_spans


def read(run):
    spans = program_spans.in_window(run, "serve.chunk")
    if not spans:
        return None
    return statistics.median(e - s for s, e in spans) / 1e6
