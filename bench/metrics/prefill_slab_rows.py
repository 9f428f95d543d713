"""Mean rows of one chunked-prefill slab in the window: the program's
counter ``prefill.slab_rows`` (one sample per slab dispatch, on the
engine's clock).  A program without the counter gives nothing."""


def read(run):
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    got = tracing.samples("prefill.slab_rows", run.window.t_open,
                          run.window.t_close)
    return sum(got) / len(got) if got else None
