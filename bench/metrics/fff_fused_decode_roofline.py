"""Roofline share of the fused FFF decode kernel (``fff_fused_decode``):
least time at the chip's peaks of every decode call in the window, over the
device time of its events in the trace.  Counts in ``bench/counts.py``
(one leaf per tree per call: a floor)."""
from bench import counts

KERNEL = r"fff_fused_decode"


def read(run):
    if run.trace is None:
        return None
    device_s, n = run.trace.kernel_seconds(KERNEL)
    sites = run.config["n_layers"]
    least = sum(sites * counts.least_seconds(
        *counts.fused_decode_call(run.config, s.decode_rows), run.peaks)
        for s in run.window.steps if s.decode_rows)
    if not n or device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
