"""Share of the traced window in which no op ran on the device."""


def read(cell, run):
    t = run["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
