"""Device time of a fit outside featurize, sort and the PCG loop, per fit,
in ms (trace): the device's busy time less the union of the intervals of
the ops whose name or scope holds ``featurize`` or ``sort`` and of the PCG
loop (as ``pcg_loop_ms`` takes it), over the window's fits.  It is the
index build beyond its sort, the first residual and the table loads, which
run eagerly.  Nothing without the loop."""
from chipbench import scopes


def _named(ops):
    loop = scopes.loop_ops(ops)
    if not loop:
        return []
    return loop + [e for e in ops if scopes.holds(e, "featurize")
                   or scopes.holds(e, "sort")]


def read(run):
    fits = len(run.window.seconds)
    named = scopes.union_seconds(run.trace, _named)
    if not fits or named <= 0:
        return None
    return (run.trace.busy_s - named) / fits * 1e3
