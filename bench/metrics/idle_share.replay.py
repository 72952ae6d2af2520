"""Percent of the replay window in which no operation ran on the chip
(1 - busy / window, from the trace)."""


def read(run):
    s = run.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
