"""Device time of the PCG loop per iteration, in ms (trace): the union of
the intervals of the ``while`` ops that enclose one of the program's table
kernels (``scopes.TABLE_KERNELS``, found by name), over the window's PCG
iterations (the model's ``cg_iters``)."""
from chipbench import scopes


def read(run):
    iters = sum(run.info["pcg_iters"])
    secs = scopes.union_seconds(run.trace, scopes.loop_ops)
    return secs / iters * 1e3 if iters and secs > 0 else None
