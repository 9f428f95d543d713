"""Whole-model share of the chip's bf16 peak in the decode-backlog cells:
the model operations of every valid prefill and decode token the window
served, counted from shapes (``bench/counts.py``), over the window's
seconds times the chip's peak."""
from bench import counts


def read(run):
    steps = run.window.steps
    if not steps:
        return None
    flops = sum(counts.model_flops(run.config,
                                   s.decode_rows + s.prefill_tokens,
                                   s.decode_ctx + s.prefill_ctx, s.emitted)
                for s in steps)
    seconds = steps[-1].t1 - run.window.t_open
    return 100.0 * flops / (seconds * run.peaks["flops_per_s"] * run.chips)
