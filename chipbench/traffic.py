"""The open-loop generator: arrival schedules, query rows and the sender.

Every seed gets the same work in another order.  Gaps between arrivals are
the N quantiles of an exponential distribution at the mix's rate (a
Poisson process's gaps, stratified), shuffled by the seed; Zipf ranks are
the N quantiles of the truncated Zipf law, shuffled by the seed.  So the
count of requests, the sum of gaps and the multiset of ranks do not depend
on the seed; which rows are asked and in what order do.

The sender is a copy of ``launch/krr_serve.py::serve_stream``'s pacing,
with latency taken from each request's due time instead of its submit
time: a stall in the sender or the server counts against every request
due during it.  How late the sender ran (send - due) is reported apart.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

WAIT_AFTER_CLOSE_S = 60.0


def poisson_due_times(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start) of round(rate·seconds)
    requests whose gaps (the first from the start) are the stratified
    exponential quantiles, shuffled; they sum to under ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps)


def zipf_ranks(n: int, pool: int, s: float, rng) -> np.ndarray:
    """n ranks in [0, pool) with P(r) proportional to (r+1)^-s, as the n
    stratified quantiles of the law, shuffled."""
    cdf = np.cumsum(np.arange(1, pool + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, (np.arange(n) + 0.5) / n)
    rng.shuffle(ranks)
    return ranks.astype(np.int64)


def zipf_top_share(k: int, pool: int, s: float) -> float:
    """Share of draws that fall on the k most popular of ``pool`` items."""
    w = np.arange(1, pool + 1, dtype=np.float64) ** -s
    return float(w[:k].sum() / w.sum())


def uniform_rows(n: int, d: int, rng, low=0.0, high=2.0) -> np.ndarray:
    return rng.uniform(low, high, size=(n, d)).astype(np.float32)


class Outcome:
    """Per-request times (perf_counter seconds) and results of one run."""

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.value = [None] * n
        self.error = [None] * n
        self._left = n
        self._lock = threading.Lock()
        self.all_done = threading.Event()
        if n == 0:
            self.all_done.set()

    def _finish(self, i: int, fut) -> None:
        t = time.perf_counter()
        exc = fut.exception()
        self.done[i] = t
        if exc is None:
            self.value[i] = fut.result()
        else:
            self.error[i] = exc
        with self._lock:
            self._left -= 1
            if self._left == 0:
                self.all_done.set()

    def latencies(self) -> np.ndarray:
        """done - due of every request answered without error."""
        ok = np.array([e is None for e in self.error]) & ~np.isnan(self.done)
        return (self.done - self.due)[ok]

    def failed(self) -> int:
        return int(sum(e is not None for e in self.error)
                   + np.isnan(self.done).sum())

    def lateness(self) -> np.ndarray:
        return (self.sent - self.due)[~np.isnan(self.sent)]


def send_open_loop(submit, rows: np.ndarray, due: np.ndarray,
                   annotate=None) -> Outcome:
    """Submit ``rows[i]`` at ``t0 + due[i]`` whatever the state of earlier
    requests; ``submit(row)`` returns a ``concurrent.futures.Future``.
    Waits until every future has resolved, at most a minute past the
    last due time."""
    out = Outcome(len(due))
    t0 = time.perf_counter()
    out.due[:] = t0 + due
    for i, row in enumerate(rows):
        while True:
            # sleep-based pacing: a busy-wait would hold the interpreter
            # lock the server's worker thread needs
            rem = out.due[i] - time.perf_counter()
            if rem <= 0:
                break
            time.sleep(min(rem, 5e-4))
        out.sent[i] = time.perf_counter()
        if annotate is not None:
            with annotate("chipbench.submit"):
                fut = submit(row)
        else:
            fut = submit(row)
        fut.add_done_callback(lambda f, i=i: out._finish(i, f))
    out.all_done.wait(max(0.0, out.due[-1] + WAIT_AFTER_CLOSE_S
                          - time.perf_counter()) if len(due) else 0.0)
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (a copy of ``serve/batcher.percentile``):
    the ceil(q/100·n)-th smallest value."""
    vals = np.sort(np.asarray(values, np.float64))
    if not len(vals):
        return float("nan")
    rank = max(0, min(len(vals) - 1, math.ceil(q / 100.0 * len(vals)) - 1))
    return float(vals[rank])
