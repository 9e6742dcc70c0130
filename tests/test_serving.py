"""Serving subsystem: artifact round-trip, warm-path predictor padding
buckets, bucket-exact cache, micro-batcher, and the operator predict split.

Exactness pins (acceptance criteria):
* export -> load -> predict is BITWISE against the in-memory model on the
  reference backend (same program, same arrays), <= 1e-6 via pallas;
* the cache-hit path BITWISE-matches the cold path (hits replay the cold
  path's own rows, and for rect any same-bucket query is the same row);
* ragged request sizes within one power-of-two padding bucket never
  recompile (pinned via the jit cache size).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (WLSHKernelSpec, get_bucket_fn, make_operator,
                        sample_lsh_params, wlsh_krr_fit, wlsh_krr_predict)
from repro.core.lsh import GammaPDF, featurize
from repro.serve import (MicroBatcher, Normalization, Predictor, bucket_sizes,
                         export_artifact, load_artifact, padding_bucket)
from repro.serve.cache import BucketKeyFn, PredictionCache


def _fit(key, n=256, d=4, m=16, bucket="rect", k_rhs=0, backend="reference"):
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1),
                          (n, k_rhs) if k_rhs else (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn(bucket))
    model = wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec, m=m,
                         lam=0.5, maxiter=100, backend=backend)
    return model, x


@pytest.fixture(scope="module")
def fitted():
    return _fit(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# operator split: featurize_buckets + predict_from_buckets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_predict_split_matches_wrapper(backend):
    key = jax.random.PRNGKey(5)
    lsh = sample_lsh_params(key, 6, 3, GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), 512, backend=backend)
    x = jax.random.uniform(jax.random.fold_in(key, 1), (100, 3)) * 2.0
    beta = jax.random.normal(jax.random.fold_in(key, 2), (100,))
    tables = op.loads(op.build_index(op.featurize(x)), beta)
    xq = jax.random.uniform(jax.random.fold_in(key, 3), (33, 3)) * 2.0
    split = op.predict_from_buckets(op.featurize_buckets(xq), tables)
    whole = op.predict_batched(tables, xq)
    # the wrapper IS the composition — identical ops, bitwise on both backends
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))


def test_predict_batched_ragged_remainder():
    """n_test not divisible by the block: every remainder shape agrees with
    the unblocked path, 1-D and multi-RHS tables alike."""
    key = jax.random.PRNGKey(6)
    lsh = sample_lsh_params(key, 5, 3, GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), 512, backend="reference")
    x = jax.random.uniform(jax.random.fold_in(key, 1), (120, 3)) * 2.0
    beta1 = jax.random.normal(jax.random.fold_in(key, 2), (120,))
    beta2 = jax.random.normal(jax.random.fold_in(key, 3), (120, 3))
    idx = op.build_index(op.featurize(x))
    for beta in (beta1, beta2):
        tables = op.loads(idx, beta)
        whole = op.predict_batched(tables, x)
        for bs in (7, 32, 119, 120, 121):   # remainder 1, 24, 1, 0, n<bs
            out = op.predict_batched(tables, x, batch_size=bs)
            assert out.shape == whole.shape
            np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_bitwise_reference(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    loaded = load_artifact(str(tmp_path / "art"))
    assert loaded.operator.backend == "reference"
    xq = x[:64]
    direct = np.asarray(wlsh_krr_predict(model, xq))
    served = np.asarray(loaded.operator.predict_batched(loaded.model.tables,
                                                        xq))
    np.testing.assert_array_equal(served, direct)
    # the arrays themselves survive npz bitwise
    np.testing.assert_array_equal(np.asarray(loaded.model.beta),
                                  np.asarray(model.beta))
    np.testing.assert_array_equal(np.asarray(loaded.model.lsh.r1),
                                  np.asarray(model.lsh.r1))


def test_artifact_roundtrip_multirhs(tmp_path):
    model, x = _fit(jax.random.PRNGKey(3), k_rhs=3)
    export_artifact(str(tmp_path / "art"), model)
    loaded = load_artifact(str(tmp_path / "art"))
    assert loaded.model.tables.shape == model.tables.shape
    np.testing.assert_array_equal(
        np.asarray(wlsh_krr_predict(loaded.model, x[:32])),
        np.asarray(wlsh_krr_predict(model, x[:32])))


def test_artifact_cross_backend_load(fitted, tmp_path):
    """A reference-fit artifact served by the pallas backend (interpret mode
    on CPU) matches to float tolerance — all backends read the same tables."""
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    loaded = load_artifact(str(tmp_path / "art"), backend="pallas")
    assert loaded.operator.backend == "pallas"
    np.testing.assert_allclose(
        np.asarray(loaded.operator.predict_batched(loaded.model.tables,
                                                   x[:32])),
        np.asarray(wlsh_krr_predict(model, x[:32])), atol=1e-6)


def test_artifact_validates_metadata(fitted, tmp_path):
    import json
    import os
    model, _ = fitted
    art = str(tmp_path / "art")
    export_artifact(art, model)
    step_dir = os.path.join(art, "step_1")
    meta_path = os.path.join(step_dir, "meta.json")
    with open(meta_path) as fh:
        meta = json.load(fh)
    # wrong table size
    bad = dict(meta, table_size=meta["table_size"] * 2)
    with open(meta_path, "w") as fh:
        json.dump(bad, fh)
    with pytest.raises(ValueError, match="tables"):
        load_artifact(art)
    # unknown bucket fn
    bad = dict(meta, bucket_name="nope")
    with open(meta_path, "w") as fh:
        json.dump(bad, fh)
    with pytest.raises(ValueError, match="bucket"):
        load_artifact(art)
    # future format version
    with open(meta_path, "w") as fh:
        json.dump(meta, fh)
    os.rename(step_dir, os.path.join(art, "step_99"))
    with pytest.raises(ValueError, match="format"):
        load_artifact(art)


def test_artifact_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_artifact(str(tmp_path / "nothing"))


def test_artifact_normalization_roundtrip(tmp_path):
    model, x = _fit(jax.random.PRNGKey(4))
    norm = Normalization(x_mean=np.full((4,), 0.5, np.float32),
                         x_std=np.full((4,), 2.0, np.float32),
                         y_mean=1.5, y_std=3.0)
    export_artifact(str(tmp_path / "art"), model, norm=norm)
    pred = Predictor()
    pred.load(str(tmp_path / "art"))
    xq = np.asarray(x[:16], np.float32)
    out = pred.predict(xq)
    direct = np.asarray(wlsh_krr_predict(
        model, (jnp.asarray(xq) - 0.5) / 2.0)) * 3.0 + 1.5
    np.testing.assert_allclose(out, direct, atol=1e-5)


def test_artifact_without_beta_serves_identically(fitted, tmp_path):
    """include_beta=False drops the O(n_train) training solution; serving
    never reads it, so predictions are unchanged (and still bitwise)."""
    model, x = fitted
    export_artifact(str(tmp_path / "full"), model)
    export_artifact(str(tmp_path / "lean"), model, include_beta=False)
    full = load_artifact(str(tmp_path / "full"))
    lean = load_artifact(str(tmp_path / "lean"))
    assert lean.model.beta.shape[0] == 0
    assert not lean.meta["has_beta"]
    xq = x[:32]
    np.testing.assert_array_equal(
        np.asarray(lean.operator.predict_batched(lean.model.tables, xq)),
        np.asarray(full.operator.predict_batched(full.model.tables, xq)))


# ---------------------------------------------------------------------------
# predictor: padding buckets + compile pinning
# ---------------------------------------------------------------------------

def test_padding_bucket_selection():
    assert [padding_bucket(b, 64) for b in (1, 2, 3, 5, 8, 9, 64, 200)] == \
        [1, 2, 4, 8, 8, 16, 64, 64]
    assert bucket_sizes(64) == (1, 2, 4, 8, 16, 32, 64)
    assert bucket_sizes(1) == (1,)
    with pytest.raises(ValueError):
        padding_bucket(0, 64)


def test_predictor_no_recompile_within_bucket(fitted, tmp_path):
    """Ragged request sizes inside one power-of-two bucket share one compile
    — pinned via the jit cache-miss count."""
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor()
    pred.load(str(tmp_path / "art"))
    xq = np.asarray(x, np.float32)
    pred.predict(xq[:5], use_cache=False)           # bucket 8: compile 1
    c0 = pred.compile_count()
    for b in (5, 6, 7, 8):                          # all bucket 8
        pred.predict(xq[:b], use_cache=False)
    assert pred.compile_count() == c0               # zero new compiles
    pred.predict(xq[:9], use_cache=False)           # bucket 16: compile 2
    assert pred.compile_count() == c0 + 1
    pred.predict(xq[:16], use_cache=False)
    assert pred.compile_count() == c0 + 1


def test_predictor_warmup_precompiles(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor()
    pred.load(str(tmp_path / "art"))
    n = pred.warmup(sizes=(1, 4, 64))               # buckets 1, 4, 64
    assert n == 3
    pred.predict(np.asarray(x[:3], np.float32))     # bucket 4: no compile
    assert pred.compile_count() == 3


def test_predictor_chunks_above_max_batch(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(max_batch=64)
    pred.load(str(tmp_path / "art"))
    xq = np.asarray(x[:200], np.float32)            # 64 + 64 + 64 + 8
    out = pred.predict(xq, use_cache=False)
    assert out.shape == (200,)
    np.testing.assert_allclose(out, np.asarray(wlsh_krr_predict(model, xq)),
                               atol=1e-6)


def test_predictor_hosts_multiple_models(tmp_path):
    m1, x1 = _fit(jax.random.PRNGKey(10))
    m2, _ = _fit(jax.random.PRNGKey(11), m=8)
    export_artifact(str(tmp_path / "a1"), m1)
    export_artifact(str(tmp_path / "a2"), m2)
    pred = Predictor()
    pred.load(str(tmp_path / "a1"))
    pred.load(str(tmp_path / "a2"))
    assert pred.artifact_ids == ["a1", "a2"]
    xq = np.asarray(x1[:32], np.float32)
    # the Predictor's padding-bucket program and wlsh_krr_predict are
    # programs of different shapes; XLA tiles the instance mean per shape,
    # so they agree to 1 ulp of the batch's largest prediction, not bitwise
    # (DESIGN.md §8)
    for aid, model in (("a1", m1), ("a2", m2)):
        want = np.asarray(wlsh_krr_predict(model, xq))
        np.testing.assert_allclose(
            pred.predict(xq, artifact_id=aid), want, rtol=0,
            atol=np.spacing(np.abs(want).max()))
    with pytest.raises(KeyError):
        pred.predict(xq, artifact_id="missing")


# ---------------------------------------------------------------------------
# bucket-exact cache
# ---------------------------------------------------------------------------

def test_numpy_bucket_keys_match_jax(fitted):
    model, x = fitted
    keyfn = BucketKeyFn(model.lsh, get_bucket_fn("rect"))
    keys, _, _ = keyfn.bucket_ids(np.asarray(x[:50], np.float32))
    feats = featurize(model.lsh, get_bucket_fn("rect"), x[:50])
    np.testing.assert_array_equal(keys[0].T, np.asarray(feats.key1))
    np.testing.assert_array_equal(keys[1].T, np.asarray(feats.key2))


def test_cache_hit_bitwise_matches_cold_path(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    cached = Predictor(cache_entries=1024)
    cold = Predictor(cache_entries=0)
    cached.load(str(tmp_path / "art"))
    cold.load(str(tmp_path / "art"))
    xq = np.asarray(x[:64], np.float32)
    first = cached.predict(xq)                       # misses: warm path
    hits = cached.predict(xq)                        # all bucket-key hits
    stats = cached.cache_stats()
    assert stats["hits"] == 64 and stats["misses"] == 64
    np.testing.assert_array_equal(hits, first)
    np.testing.assert_array_equal(hits, cold.predict(xq))


def test_cache_same_bucket_query_is_exact_for_rect(fitted, tmp_path):
    """rect weight is constant inside a bucket, so a DIFFERENT point in the
    same m buckets must hit AND the replayed value must equal that point's
    own cold-path prediction bitwise — the cache is exact, not approximate."""
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(cache_entries=1024)
    pred.load(str(tmp_path / "art"))
    keyfn = BucketKeyFn(model.lsh, get_bucket_fn("rect"))
    x0 = np.asarray(x[:1], np.float32)
    # nudge within the bucket: accept the perturbation only if every one of
    # the m bucket ids is unchanged
    x1 = None
    for eps in (1e-4, 1e-5, 1e-6):
        cand = (x0 + eps).astype(np.float32)
        if keyfn(cand) == keyfn(x0) and not np.array_equal(cand, x0):
            x1 = cand
            break
    assert x1 is not None, "no same-bucket perturbation found"
    cold = np.asarray(pred.predict(x1[0], use_cache=False))
    pred.predict(x0[0])                              # insert x0's row
    st0 = pred.cache_stats()
    out = pred.predict(x1[0])                        # different point, same key
    st1 = pred.cache_stats()
    assert st1["hits"] == st0["hits"] + 1
    np.testing.assert_array_equal(out, cold)


def test_cache_nonrect_requires_identical_point(tmp_path):
    """tent weights vary inside a bucket: the key carries the residual, so a
    same-bucket-different-point query must MISS (a hit there would be wrong)."""
    model, x = _fit(jax.random.PRNGKey(7), bucket="tent")
    keyfn = BucketKeyFn(model.lsh, get_bucket_fn("tent"))
    x0 = np.asarray(x[:1], np.float32)
    x1 = (x0 + 1e-5).astype(np.float32)
    assert keyfn(x0) == keyfn(x0)                    # deterministic
    assert keyfn(x1) != keyfn(x0)
    assert not keyfn.exact_within_bucket


def test_cache_keys_nonfinite_rows_warning_free(fitted):
    """NaN/inf queries fall back to raw-identity keys: distinct garbage rows
    never alias, identical ones still hit — and the f32->int32 cast they
    trigger must not leak a RuntimeWarning into the serving path."""
    import warnings

    model, _ = fitted
    keyfn = BucketKeyFn(model.lsh, get_bucket_fn("rect"))
    bad = np.zeros((3, 4), np.float32)
    bad[0, 0], bad[1, 1], bad[2, 2] = np.nan, np.inf, 3e9   # |h| >= 2^31
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        keys = keyfn(bad)
    assert all(k.startswith(b"!raw") for k in keys)
    assert len(set(keys)) == 3                       # no aliasing
    assert keyfn(bad) == keys                        # deterministic


def test_cache_lru_eviction_and_stats():
    cache = PredictionCache(max_entries=2)
    cache.put_many([b"a", b"b"], [np.float32(1), np.float32(2)])
    assert cache.get_many([b"a"]) == [np.float32(1)]   # refreshes a
    cache.put_many([b"c"], [np.float32(3)])            # evicts b (LRU)
    out = cache.get_many([b"b", b"a", b"c"])
    assert out[0] is None and out[1] == 1 and out[2] == 3
    st = cache.stats()
    assert st["evictions"] == 1 and st["entries"] == 2
    with pytest.raises(ValueError):
        PredictionCache(max_entries=0)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

def test_batcher_roundtrips_and_coalesces(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(cache_entries=4096)
    pred.load(str(tmp_path / "art"))
    pred.warmup(sizes=bucket_sizes(16))
    xq = np.asarray(x[:50], np.float32)
    expect = {i: np.asarray(pred.predict(xq[i])) for i in range(50)}
    pred.clear_cache()
    with MicroBatcher(lambda xb: pred.predict(xb), max_batch=16,
                      max_wait_us=5000) as mb:
        futures = [mb.submit(xq[i % 50]) for i in range(200)]
        results = [f.result(timeout=30) for f in futures]
        stats = mb.stats()
    assert stats["served"] == 200
    assert stats["batches"] < 200          # actually coalesced
    assert stats["mean_batch"] > 1.0
    assert 0 < stats["p50_us"] <= stats["p99_us"]
    for i, got in enumerate(results):
        np.testing.assert_allclose(np.asarray(got), expect[i % 50], atol=1e-6)


def test_batcher_deadline_flushes_lone_request(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor()
    pred.load(str(tmp_path / "art"))
    pred.warmup(sizes=(1,))
    with MicroBatcher(lambda xb: pred.predict(xb), max_batch=64,
                      max_wait_us=1000) as mb:
        fut = mb.submit(np.asarray(x[0], np.float32))
        out = fut.result(timeout=10)       # resolves without 63 more requests
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(pred.predict(x[0])))


def test_batcher_propagates_predict_errors():
    def boom(xb):
        raise RuntimeError("model exploded")
    with MicroBatcher(boom, max_batch=4, max_wait_us=100) as mb:
        fut = mb.submit(np.zeros((3,), np.float32))
        with pytest.raises(RuntimeError, match="model exploded"):
            fut.result(timeout=10)


def test_batcher_rejects_wrong_dim_without_failing_batch():
    """A malformed request is refused at ITS submit() — the requests already
    coalescing around it still resolve normally."""
    def echo(xb):
        return np.zeros((len(xb),), np.float32)

    with MicroBatcher(echo, max_batch=8, max_wait_us=5000, dim=3) as mb:
        good = [mb.submit(np.zeros((3,), np.float32)) for _ in range(4)]
        with pytest.raises(ValueError, match="features"):
            mb.submit(np.zeros((7,), np.float32))
        assert all(f.result(timeout=10) == 0.0 for f in good)
    # without an explicit dim the first accepted request locks it in
    with MicroBatcher(echo, max_batch=8, max_wait_us=100) as mb:
        mb.submit(np.zeros((5,), np.float32)).result(timeout=10)
        with pytest.raises(ValueError, match="features"):
            mb.submit(np.zeros((4,), np.float32))


def test_batcher_close_drains_and_rejects_new():
    served = []

    def slow(xb):
        served.append(len(xb))
        return np.zeros((len(xb),), np.float32)

    mb = MicroBatcher(slow, max_batch=8, max_wait_us=50)
    futs = [mb.submit(np.zeros((2,), np.float32)) for _ in range(20)]
    mb.close()
    assert all(f.done() for f in futs)
    assert sum(served) == 20
    with pytest.raises(RuntimeError):
        mb.submit(np.zeros((2,), np.float32))


def test_batcher_threaded_submitters(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(cache_entries=4096)
    pred.load(str(tmp_path / "art"))
    pred.warmup(sizes=bucket_sizes(32))
    xq = np.asarray(x[:40], np.float32)
    expect = np.asarray(pred.predict(xq))
    errs = []
    with MicroBatcher(lambda xb: pred.predict(xb), max_batch=32,
                      max_wait_us=2000) as mb:
        def client(rows):
            try:
                for i in rows:
                    got = mb.submit(xq[i]).result(timeout=30)
                    np.testing.assert_allclose(np.asarray(got), expect[i],
                                               atol=1e-6)
            except Exception as e:          # surfaces in the main thread
                errs.append(e)
        threads = [threading.Thread(target=client,
                                    args=(range(j, 40, 4),))
                   for j in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs


# ---------------------------------------------------------------------------
# degraded-mode serving (DESIGN.md §9): crash, shed, deadlines, health
# ---------------------------------------------------------------------------

def test_batcher_worker_crash_fails_all_futures_and_fast_fails_submit():
    """A worker-thread death (injected OUTSIDE the predict try/except) must
    fail every in-flight and queued future with WorkerCrashed and make later
    submits raise immediately — nobody ever hangs on a dead worker."""
    from repro.serve import WorkerCrashed
    from repro.testing import crash_worker
    gate = threading.Event()

    def slow_predict(xb):
        gate.wait(5.0)
        return np.zeros((xb.shape[0],), np.float32)

    mb = MicroBatcher(slow_predict, max_batch=4, max_wait_us=500, dim=2)
    crash_worker(mb)
    futs = [mb.submit(np.zeros(2, np.float32)) for _ in range(6)]
    gate.set()
    for f in futs:
        with pytest.raises(WorkerCrashed):
            f.result(timeout=10.0)
    assert mb.stats()["crashed"]
    with pytest.raises(WorkerCrashed):       # fail-fast, not a queue hang
        mb.submit(np.zeros(2, np.float32))
    mb.close()                               # idempotent after a crash


def test_batcher_load_shedding_returns_overloaded():
    """Submits past max_queue fail at once with Overloaded carrying the
    queue depth; accepted requests still serve correctly afterwards."""
    from repro.serve import Overloaded
    gate = threading.Event()

    def gated_predict(xb):
        gate.wait(10.0)
        return np.arange(xb.shape[0]).astype(np.float32)

    with MicroBatcher(gated_predict, max_batch=1, max_wait_us=100,
                      dim=2, max_queue=2) as mb:
        futs = [mb.submit(np.zeros(2, np.float32)) for _ in range(12)]
        shed = [f for f in futs if f.done()
                and isinstance(f.exception(), Overloaded)]
        assert shed, "nothing shed at queue depth 2 under a blocked worker"
        assert shed[0].exception().queue_depth >= 2
        gate.set()
        served = 0
        for f in futs:
            if f in shed:
                continue
            assert f.result(timeout=10.0) is not None
            served += 1
        stats = mb.stats()
    assert stats["shed"] == len(shed)
    assert stats["shed_rate"] == pytest.approx(len(shed) / 12)
    assert served == 12 - len(shed)


def test_batcher_deadline_expires_queued_requests():
    """A request whose deadline budget elapses while queued fails with
    DeadlineExceeded at flush time, BEFORE costing a predict call."""
    from repro.serve import DeadlineExceeded
    gate = threading.Event()
    calls = []

    def gated_predict(xb):
        calls.append(xb.shape[0])
        gate.wait(10.0)
        return np.zeros((xb.shape[0],), np.float32)

    with MicroBatcher(gated_predict, max_batch=1, max_wait_us=100,
                      dim=2) as mb:
        f1 = mb.submit(np.zeros(2, np.float32))          # occupies worker
        f2 = mb.submit(np.ones(2, np.float32), deadline_us=10_000)
        time.sleep(0.1)                                  # budget burns out
        gate.set()
        assert f1.result(timeout=10.0) is not None
        with pytest.raises(DeadlineExceeded) as ei:
            f2.result(timeout=10.0)
        assert ei.value.waited_s >= 0.01
        stats = mb.stats()
    assert stats["deadline_expired"] == 1
    assert calls.count(1) == 1      # the expired request never ran predict


def test_predictor_rejects_nan_query_structured(fitted, tmp_path):
    """A NaN/Inf query row surfaces as InvalidRequest — never a silently-NaN
    prediction, and never a poisoned cache entry replayed to later calls."""
    from repro.serve import InvalidRequest
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(cache_entries=64)
    pred.load(str(tmp_path / "art"))
    bad = np.asarray(x[:4], np.float32).copy()
    bad[2, 0] = np.nan
    with pytest.raises(InvalidRequest, match=r"\[2\]"):
        pred.predict(bad)
    with pytest.raises(InvalidRequest):
        pred.predict(np.full((3,), np.inf, np.float32))
    # the clean rows still serve, and health recorded the rejections
    out = pred.predict(np.asarray(x[:4], np.float32))
    assert np.isfinite(out).all()
    h = pred.health()
    assert h["errors"] == 2 and "InvalidRequest" in h["last_error"]


def test_predictor_health_snapshot_with_batcher(fitted, tmp_path):
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor()
    aid = pred.load(str(tmp_path / "art"))
    with MicroBatcher(lambda xb: pred.predict(xb), max_batch=8,
                      max_wait_us=500) as mb:
        pred.attach_batcher(mb)
        for row in np.asarray(x[:8], np.float32):
            mb.submit(row).result(timeout=10.0)
        h = pred.health()
    assert h["ok"] and h["models"] == [aid]
    assert h["requests"] >= 1 and h["errors"] == 0
    assert h["batcher"]["queue_depth"] == 0
    assert not h["batcher"]["crashed"]


def test_predictor_fault_plan_drives_serve_failures(fitted, tmp_path):
    """FaultPlan(serve_fail_every=N) fails every Nth warm call with
    FaultInjected — the hook the shed/deadline stress tests hang load on."""
    from repro.errors import FaultInjected
    from repro.testing import FaultPlan
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    pred = Predictor(fault_plan=FaultPlan(serve_fail_every=2))
    pred.load(str(tmp_path / "art"))
    xq = np.asarray(x[:2], np.float32)
    assert np.isfinite(pred.predict(xq)).all()           # call 1 clean
    with pytest.raises(FaultInjected):                   # call 2 injected
        pred.predict(xq)
    assert np.isfinite(pred.predict(xq)).all()           # call 3 clean
    assert pred.health()["errors"] == 1


def test_artifact_load_retries_transient_io(fitted, tmp_path, monkeypatch):
    """Transient I/O failures (flaky filesystem) retry with backoff;
    validation errors never retry.  retries=0 keeps historical behavior."""
    import repro.serve.artifact as art_mod
    model, x = fitted
    export_artifact(str(tmp_path / "art"), model)
    real_once = art_mod._load_artifact_once
    fails = {"n": 2}

    def flaky(directory, **kw):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient read failure")
        return real_once(directory, **kw)

    monkeypatch.setattr(art_mod, "_load_artifact_once", flaky)
    with pytest.raises(OSError):
        load_artifact(str(tmp_path / "art"))             # no retries: raises
    fails["n"] = 2
    loaded = load_artifact(str(tmp_path / "art"), retries=3,
                           retry_backoff_s=0.01)
    assert loaded.artifact_id == "art"
    assert fails["n"] == 0


def test_artifact_rejects_nonfinite_tables(fitted, tmp_path):
    """A poisoned artifact (NaN in the tables) is refused at load — the
    predictor can never host a model that answers NaN to every query."""
    model, x = fitted
    poisoned = model._replace(
        tables=jnp.asarray(model.tables).at[0, 0].set(jnp.nan))
    export_artifact(str(tmp_path / "bad"), poisoned)
    with pytest.raises(ValueError, match="non-finite"):
        load_artifact(str(tmp_path / "bad"))
