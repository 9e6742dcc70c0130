"""Device time of the matvec's table kernel per PCG iteration, in ms
(trace): the union of the intervals of the ops named by one of the
program's table kernels (``scopes.TABLE_KERNELS``) that lie inside the PCG
loop (as ``pcg_loop_ms`` takes it), over the window's PCG iterations."""
from chipbench import scopes


def read(run):
    iters = sum(run.info["pcg_iters"])
    secs = scopes.union_seconds(run.trace, scopes.loop_kernels)
    return secs / iters * 1e3 if iters and secs > 0 else None
