"""Device time of the featurize kernel per fit, in ms (trace): ops whose
name, JAX name stack or jitted program holds ``featurize``."""
NAME = "featurize"


def read(run):
    fits = len(run.window.seconds)
    secs = run.trace.op_seconds(NAME)
    return secs / fits * 1e3 if fits and secs > 0 else None
