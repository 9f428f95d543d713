"""Mean distinct leaves read per FFF site per decode call in the window:
the program's counter ``fff.decode_leaves`` (one sample per decode step,
on the engine's clock).  A program without the counter gives nothing."""


def read(run):
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    got = tracing.samples("fff.decode_leaves", run.window.t_open,
                          run.window.t_close)
    return sum(got) / len(got) if got else None
