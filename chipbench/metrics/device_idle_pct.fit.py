"""Share of the traced window in which no operation ran on the chip, in %
(trace): 100 * (1 - union of op intervals / window)."""


def read(run):
    return 100.0 * run.trace.idle_share() if run.trace.window_s > 0 else None
