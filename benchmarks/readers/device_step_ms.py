"""Device time of one optimizer step: the seconds of the traced stretch
in which an op ran on the device (``trace.busy_s``) over the optimizer
steps of that stretch, in ms. Needs nothing of the program but the
trace."""

from benchmarks.readers.scope_ms import traced_steps


def read(cell, run):
    steps = traced_steps(cell, run)
    return 1e3 * run["trace"].busy_s / steps if steps else None
