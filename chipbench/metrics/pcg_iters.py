"""PCG iterations per fit, mean over the window's fits (the model's own
``cg_iters``)."""


def read(run):
    iters = run.info["pcg_iters"]
    return sum(iters) / len(iters) if iters else None
