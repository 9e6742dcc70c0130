"""Perf regression gates over the committed BENCH_*.json files (--runslow).

Reruns the matvec benchmark section at the committed sizes and fails when
``reference_us`` or ``fused_us`` regresses more than 1.3x; reruns the
serving warm/cached single-query sections against BENCH_serving.json and
additionally pins the subsystem's two structural speedups (warm >= 5x cold,
cache hit >= 10x warm) — see ``benchmarks/check_regression.py`` for the
standalone CLI form.
"""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

pytestmark = pytest.mark.slow


def test_matvec_perf_no_regression():
    from benchmarks.check_regression import DEFAULT_BASELINE, check
    assert DEFAULT_BASELINE.exists(), "committed BENCH_matvec.json missing"
    failures, rows = check()
    if not rows:
        pytest.skip("baseline recorded on a different platform")
    assert not failures, "\n".join(failures)


def test_hashjoin_distributed_no_regression():
    """Acceptance pin (PR 6): rerun the distributed benchmark section at the
    committed (n, shards) cells and fail when ``hashjoin_iter_us`` regresses
    >2x, when it is not >= 2x below the carried-forward pre-fusion routing
    cost (``hashjoin_prefuse_iter_us``), or when the k=8 multi-RHS block
    costs >= 2x a single-RHS iteration per column.  Spawns fake-CPU-mesh
    subprocesses — minutes-scale, hence slow-marked."""
    from benchmarks.check_regression import (DEFAULT_BASELINE,
                                             check_distributed)
    assert DEFAULT_BASELINE.exists(), "committed BENCH_matvec.json missing"
    failures, fresh = check_distributed()
    if not fresh:
        pytest.skip("no comparable distributed baseline (platform differs "
                    "or section absent)")
    assert not failures, "\n".join(failures)


def test_serving_latency_no_regression():
    from benchmarks.check_regression import (DEFAULT_SERVING_BASELINE,
                                             check_serving)
    assert DEFAULT_SERVING_BASELINE.exists(), \
        "committed BENCH_serving.json missing"
    failures, best = check_serving()
    if not best:
        pytest.skip("baseline recorded on a different platform")
    assert not failures, "\n".join(failures)


def test_sharded_serving_no_regression():
    """Acceptance pin (PR 8): rerun the sharded 2x2 serving section against
    BENCH_serving.json's ``sharded`` cell and fail when the warm batch-64
    p50 regresses >2x or drifts beyond 3x the single-host warm p50 measured
    in the same child (the ratio is machine-speed immune).  Spawns a
    4-fake-CPU-device subprocess — minutes-scale, hence slow-marked."""
    from benchmarks.check_regression import (DEFAULT_SERVING_BASELINE,
                                             check_sharded_serving)
    assert DEFAULT_SERVING_BASELINE.exists(), \
        "committed BENCH_serving.json missing"
    failures, fresh = check_sharded_serving()
    if not fresh:
        pytest.skip("no comparable sharded baseline (platform differs "
                    "or section absent)")
    assert not failures, "\n".join(failures)


def test_lifecycle_no_regression():
    """Acceptance pin (self-healing runtime): rerun the lifecycle section
    against BENCH_serving.json's ``lifecycle`` cell and fail when a live
    swap recompiles warm buckets (``swap_compile_delta`` != 0), post-swap
    p50 drifts beyond 2x steady p50 (machine-speed-immune ratio), or forced
    rollback-to-first-healthy-prediction regresses >2x the baseline.
    In-process, but fits + exports several versions — hence slow-marked."""
    from benchmarks.check_regression import (DEFAULT_SERVING_BASELINE,
                                             check_lifecycle)
    assert DEFAULT_SERVING_BASELINE.exists(), \
        "committed BENCH_serving.json missing"
    failures, fresh = check_lifecycle()
    if not fresh:
        pytest.skip("no comparable lifecycle baseline (platform differs "
                    "or section absent)")
    if "error" in fresh:
        pytest.skip(f"lifecycle measurement failed: {fresh['error'][:120]}")
    assert not failures, "\n".join(failures)


def test_serving_structural_speedups():
    """Acceptance pins: the warm path must beat the compile-included cold
    first call by >= 5x, and a bucket-exact cache hit must beat the warm
    featurize+readout path by >= 10x.  Best-of-3 on the cache ratio — the
    shared-container timing distribution is bursty and only the quiet mode
    is reproducible (see benchmarks/common.time_fn)."""
    from benchmarks import bench_serving
    res = bench_serving.run(iters=100, batch_requests=0, offered_qps=(),
                            repeats=3)
    warm = res["warm_speedup_vs_cold"]
    cache = res["cache_speedup_vs_warm"]
    assert warm >= 5.0, \
        f"warm path only {warm:.1f}x faster than cold first call"
    assert cache >= 10.0, \
        f"cache hit only {cache:.1f}x faster than warm path"
