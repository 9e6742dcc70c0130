"""Mean time the batcher spends filling a batch, in ms (trace): the mean
duration of the program's ``serve.batch_fill`` host events that start in
the window, each from the batch's first request dequeued to its
dispatch."""
from chipbench import scopes


def read(run):
    evs = scopes.host_events(run.trace, "serve.batch_fill")
    return sum(e.end - e.start for e in evs) / len(evs) * 1e-6 \
        if evs else None
