"""Mean host time of a batch's predict call, in ms (trace): over the
program's ``serve.batch_predict`` host events that start in the window,
the mean of the event's duration less the device's busy time inside it
(host preparation, cache probe, dispatch, the copy of the answers back)."""
from chipbench import scopes


def read(run):
    red = run.trace
    evs = scopes.host_events(red, "serve.batch_predict")
    if not evs:
        return None
    host = [(e.end - e.start) * 1e-9 - scopes.busy_within(red, e.start, e.end)
            for e in evs]
    return sum(host) / len(host) * 1e3
