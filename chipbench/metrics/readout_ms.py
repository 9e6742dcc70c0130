"""Device busy time per device batch over the window, in ms (trace busy
time over the program's batch counter).  No kernel name is read."""


def read(run):
    b = run.info["batches"]
    return run.trace.busy_s / b * 1e3 if b and run.trace.busy_s > 0 else None
