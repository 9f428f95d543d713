"""Median of the engine's own decode latencies in the window: dispatch of
the decode step to its logits on the host (``engine.decode_lat``)."""
import statistics


def read(run):
    lat = run.window.decode_lat
    return 1e3 * statistics.median(lat) if lat else None
