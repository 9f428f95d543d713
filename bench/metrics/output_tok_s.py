"""Generated tokens emitted inside the window, over the window's seconds."""


def read(run):
    w = run.window
    n = sum(1 for s in w.requests.values() for t in s.token_times
            if w.t_open <= t < w.t_close)
    return n / (w.t_close - w.t_open)
