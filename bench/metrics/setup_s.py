"""Process start to the opening of the window: imports, device start-up,
weights, engine, and the warm-up that compiles or loads every program."""


def read(run):
    return run.setup_s
