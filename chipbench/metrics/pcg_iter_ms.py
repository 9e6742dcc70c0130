"""Device busy time of the window's fits outside featurize and sort, over
their PCG iterations, in ms (trace).  No kernel name is read for the
matvec, so the number survives a change of kernel."""


def read(run):
    iters = sum(run.info["pcg_iters"])
    t = run.trace
    rest = t.busy_s - t.op_seconds("featurize") - t.op_seconds("sort")
    return rest / iters * 1e3 if iters and rest > 0 else None
