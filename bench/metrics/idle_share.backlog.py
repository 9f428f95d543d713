"""Share of the traced window in which no operation ran on the device, in
the closed-loop decode-backlog cells (what the host loop costs the chip)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
