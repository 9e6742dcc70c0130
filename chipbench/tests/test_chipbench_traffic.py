"""The open-loop generator: schedules from the seed, the Zipf draw, and
latency taken from the due time."""
import math
import queue
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from chipbench import harness, traffic


def test_same_seed_same_schedule_and_every_seed_the_same_work():
    a = traffic.poisson_due_times(95.0, 45.0, harness.seed_rng(7, 4))
    b = traffic.poisson_due_times(95.0, 45.0, harness.seed_rng(7, 4))
    c = traffic.poisson_due_times(95.0, 45.0, harness.seed_rng(2**40 + 3, 4))
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == round(95.0 * 45.0)
    assert a[0] > 0.0 and np.all(np.diff(a) > 0)
    assert not np.array_equal(a, c)
    # the same gaps in another order: the same span
    gaps = [np.sort(np.diff(np.concatenate([[0.0], t]))) for t in (a, c)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert a[-1] == pytest.approx(c[-1]) and 44.0 < a[-1] < 45.0


def test_every_seed_fits_the_same_keys_in_another_order():
    from chipbench import fit_loop
    size = 5
    pool = fit_loop.key_pool(size)
    assert len(pool) == size + 1            # the last is the warm-up's
    assert len({tuple(np.asarray(k).tolist()) for k in pool}) == size + 1
    np.testing.assert_array_equal(pool[2], fit_loop.key_pool(size)[2])
    orders = {seed: [fit_loop.key_index(seed, size, i) for i in range(3 * size)]
              for seed in (7, 8, 2**40 + 3)}
    for order in orders.values():
        # whole rounds: each round takes every key of the pool once
        for r in range(3):
            assert sorted(order[r * size:(r + 1) * size]) == list(range(size))
    assert orders[7] == [fit_loop.key_index(7, size, i)
                         for i in range(3 * size)]
    assert orders[7] != orders[8] != orders[2**40 + 3]


def test_zipf_top_share_is_as_computed():
    pool, n, s = 65536, 20000, 1.1
    ranks = traffic.zipf_ranks(n, pool, s, harness.seed_rng(3, 4))
    for k in (1, 64, 4096):
        share = traffic.zipf_top_share(k, pool, s)
        # stratified quantiles: off by at most one draw per boundary
        assert abs(np.mean(ranks < k) - share) <= 1.0 / n + 1e-12
    # the issue's LFU bound: the top 4,096 of 65,536 hold ~86% of draws
    expect = (math.fsum(r ** -s for r in range(1, 4097))
              / math.fsum(r ** -s for r in range(1, pool + 1)))
    assert traffic.zipf_top_share(4096, pool, s) == pytest.approx(expect)
    assert expect == pytest.approx(0.855, abs=0.001)


def test_a_stalled_server_shows_as_latency_from_the_due_time():
    stall = 0.3
    work: queue.Queue = queue.Queue()

    def worker():
        first = True
        while (item := work.get()) is not None:
            fut, row = item
            if first:
                time.sleep(stall)     # the server stalls on its first request
                first = False
            fut.set_result(float(row[0]))

    th = threading.Thread(target=worker)
    th.start()

    def submit(row):
        fut = Future()
        work.put((fut, row))
        return fut

    due = np.arange(20) * 0.01                 # 100 req/s for 0.2 s
    rows = np.arange(20, dtype=np.float32)[:, None]
    try:
        out = traffic.send_open_loop(submit, rows, due)
    finally:
        work.put(None)
        th.join(5)
    assert not th.is_alive()
    assert out.failed() == 0
    lat = out.latencies()
    # every request due during the stall waits for its end: from its due
    # time, not from a later submit
    assert np.all(lat[due < stall] >= stall - due[due < stall] - 1e-3)
    assert traffic.percentile(out.lateness(), 100) < stall / 2
    assert [v for v in out.value] == list(range(20))


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert traffic.percentile(vals, 50) == 50
    assert traffic.percentile(vals, 95) == 95
    assert traffic.percentile(vals, 100) == 100
    assert np.isnan(traffic.percentile([], 50))
