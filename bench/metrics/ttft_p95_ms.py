"""95th percentile of the time to first token over every request that
arrived in the window (open loop: timed from when it was due), served
until its first token by the drain.  Requests with none count as failed."""
import numpy as np


def read(run):
    w = run.window
    ttft = [s.first_token - s.arrival for s in w.requests.values()
            if w.t_open <= s.arrival < w.t_close and s.first_token is not None]
    return 1e3 * float(np.percentile(ttft, 95)) if ttft else None
