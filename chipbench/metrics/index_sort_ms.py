"""Device time of sort operations per fit, in ms (trace): ops whose name,
JAX name stack or jitted program holds ``sort``.  The slot sort of the
blocked layout is the only sort a fit runs."""
NAME = "sort"


def read(run):
    fits = len(run.window.seconds)
    secs = run.trace.op_seconds(NAME)
    return secs / fits * 1e3 if fits and secs > 0 else None
