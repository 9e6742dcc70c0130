"""Mean wait of a request in the batcher, in ms (program counter): the sum
over the count of the program's ``serve_queue_wait_us`` histogram, each
request's wait from its submit to its batch's flush (the batch's fill
included).  The window's ``MicroBatcher`` is the run's only one: set-up
calls the ``Predictor`` directly."""
from repro import obs


def read(run):
    fam = obs.REGISTRY.snapshot().get("serve_queue_wait_us")
    series = fam["series"] if fam else []
    count = sum(s["count"] for s in series)
    return sum(s["sum"] for s in series) / count * 1e-3 if count else None
