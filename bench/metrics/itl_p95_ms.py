"""95th percentile over every gap between two consecutive output tokens of
one request, both emitted inside the window."""
import numpy as np


def read(run):
    w = run.window
    gaps = []
    for s in w.requests.values():
        t = [x for x in s.token_times if w.t_open <= x < w.t_close]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
