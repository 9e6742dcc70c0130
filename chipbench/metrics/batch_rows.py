"""Rows that reached the device per device batch: cache misses over padded
batches served, over the window (program counters)."""


def read(run):
    b = run.info["batches"]
    return run.info["misses"] / b if b else None
