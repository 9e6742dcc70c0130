"""Warm-path predictor: multi-model hosting with padding-bucket compilation.

One hosted model = one jitted ``featurize_buckets -> predict_from_buckets``
program (normalization folded in) whose compilation is keyed on the request
shape.  Ragged request sizes would retrace per size, so every batch is padded
up to a power-of-two PADDING BUCKET (1, 2, 4, ... max_batch) before entering
jit: the jit cache then holds at most log2(max_batch)+1 entries per model and
a new request size within an existing bucket NEVER recompiles (pinned by
tests via the jit cache-miss count).  Batches above ``max_batch`` are served
in max_batch-sized chunks — compile cost stays bounded no matter what the
batcher coalesces.

The predictor optionally fronts the jit path with the bucket-exact cache
(serve/cache.py): rows whose bucket key is cached skip featurize+readout
entirely; the remaining rows run the warm path and their results are
inserted.  Hits are exact — the cache stores the warm path's own output.
"""
from __future__ import annotations

import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .artifact import LoadedArtifact, load_artifact
from .. import obs
from ..core.bucket_fns import get_bucket_fn
from ..errors import InvalidRequest
from ..testing.faults import FaultPlan, serve_fault
from .cache import BucketKeyFn, PredictionCache

DEFAULT_MAX_BATCH = 1024


def padding_bucket(n: int, max_batch: int) -> int:
    """Smallest power of two >= n, capped at max_batch (callers chunk above
    the cap)."""
    if n <= 0:
        raise ValueError(f"need a positive batch, got {n}")
    return min(1 << (n - 1).bit_length(), max_batch)


def bucket_sizes(limit: int) -> tuple[int, ...]:
    """Every padding bucket up to ``limit``: (1, 2, 4, ..., >= limit).  Feed
    to ``Predictor.warmup`` so a batcher bounded by ``limit`` never hits a
    compile mid-traffic."""
    if limit <= 0:
        raise ValueError(f"need a positive limit, got {limit}")
    return tuple(1 << p for p in range((limit - 1).bit_length() + 1))


class _HostedModel(NamedTuple):
    loaded: LoadedArtifact
    predict_fn: object       # jitted (tables, x_padded) -> yhat_padded
    keyfn: BucketKeyFn
    cache: PredictionCache | None
    keymemo: PredictionCache | None   # raw query bytes -> bucket key: skips
                                      # the numpy hash for repeat queries


class Predictor:
    """Hosts fitted models keyed by artifact id and serves point predictions.

    ``predict`` accepts a (b, d) request batch (or a single (d,) point) and
    returns numpy predictions: (b,) for a single-target model, (b, k) for a
    multi-RHS fit.  ``cache_entries > 0`` enables the bucket-exact cache per
    model; ``backend`` overrides the recorded fit backend at load time.
    """

    def __init__(self, *, backend: str | None = None,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 cache_entries: int = 0,
                 fault_plan: FaultPlan | None = None):
        if max_batch & (max_batch - 1) or max_batch <= 0:
            raise ValueError(f"max_batch must be a power of two, "
                             f"got {max_batch}")
        self.backend = backend
        self.max_batch = int(max_batch)
        self.cache_entries = int(cache_entries)
        self.fault_plan = fault_plan    # chaos tests: warm-path stall/fail
        self._models: dict[str, _HostedModel] = {}
        self._default_id: str | None = None
        self._lock = threading.Lock()
        self._n_predicts = 0            # warm-path calls (drives serve_fault)
        self._n_requests = 0
        self._n_errors = 0
        self._last_error: str | None = None
        self._batcher = None            # attached MicroBatcher, for health()
        # registry children resolved once; health() keeps reading the
        # per-instance counters above (API-stable exact values), the global
        # registry gets the same increments for scraping
        self._m_requests = obs.counter(
            "serve_requests_total", "predict() calls accepted").labels()
        self._m_errors = obs.counter(
            "serve_errors_total", "predict() calls that raised").labels()
        self._m_predict_us = obs.histogram(
            "serve_predict_us", "end-to-end predict() wall time").labels()
        self._m_warm_us = obs.histogram(
            "serve_warm_compute_us",
            "jitted warm-path wall time per call").labels()
        self._m_probe_us = obs.histogram(
            "serve_cache_probe_us",
            "bucket-key + cache probe wall time").labels()
        self._m_hits = obs.counter(
            "serve_cache_hits_total",
            "query rows served from the cache").labels()
        self._m_misses = obs.counter(
            "serve_cache_misses_total",
            "query rows that ran the warm path").labels()
        self._m_bucket = obs.counter(
            "serve_padding_bucket_total",
            "batches served per power-of-two padding bucket",
            labels=("bucket",))
        self._bucket_children: dict = {}   # bucket -> bound counter child
        # flat pre-bound timers, not full spans: these are the per-request
        # sites that pay the metrics-on/off <=1.05x p50 pin
        self._t_predict = obs.timer("serve.predict",
                                    to_histogram=self._m_predict_us)
        self._t_warm = obs.timer("serve.warm_compute",
                                 to_histogram=self._m_warm_us)
        self._t_probe = obs.timer("serve.cache_probe",
                                  to_histogram=self._m_probe_us)

    # -- model hosting ------------------------------------------------------

    def load(self, directory: str, *, artifact_id: str | None = None,
             retries: int = 0, retry_backoff_s: float = 0.05) -> str:
        """Load an artifact from disk and host it; returns its id.

        ``retries`` re-attempts transient I/O failures (flaky NFS, an
        exporter's rename racing the read) with exponential backoff —
        validation errors are never retried, a malformed artifact stays
        malformed."""
        loaded = load_artifact(directory, backend=self.backend,
                               artifact_id=artifact_id, retries=retries,
                               retry_backoff_s=retry_backoff_s)
        return self.add_model(loaded)

    def add_model(self, loaded: LoadedArtifact) -> str:
        """Host an already-loaded artifact (id from the artifact)."""
        op, norm = loaded.operator, loaded.norm

        def fn(tables, x):
            x = jnp.asarray(x, jnp.float32)
            if norm is not None:
                x = (x - jnp.asarray(norm.x_mean)) / jnp.asarray(norm.x_std)
            out = op.predict_from_buckets(op.featurize_buckets(x), tables)
            if norm is not None:
                out = out * jnp.float32(norm.y_std) + jnp.float32(norm.y_mean)
            return out

        hosted = _HostedModel(
            loaded=loaded, predict_fn=jax.jit(fn),
            keyfn=BucketKeyFn(loaded.model.lsh,
                              get_bucket_fn(loaded.model.bucket_name)),
            cache=(PredictionCache(self.cache_entries)
                   if self.cache_entries > 0 else None),
            keymemo=(PredictionCache(self.cache_entries)
                     if self.cache_entries > 0 else None))
        with self._lock:
            self._models[loaded.artifact_id] = hosted
            if self._default_id is None:
                self._default_id = loaded.artifact_id
        obs.counter("serve_models_loaded_total",
                    "artifacts hosted over the process lifetime").inc()
        if hosted.cache is not None:
            # pull-time gauges: cache state is read only when scraped, so
            # hosting a model adds zero per-request cost
            cache = hosted.cache
            obs.gauge("serve_cache_entries", "live prediction-cache entries",
                      labels=("model",)).labels(loaded.artifact_id).set_fn(
                lambda cache=cache: cache.stats()["entries"])
            obs.gauge("serve_cache_evictions",
                      "prediction-cache evictions to date",
                      labels=("model",)).labels(loaded.artifact_id).set_fn(
                lambda cache=cache: cache.stats()["evictions"])
        return loaded.artifact_id

    def _hosted(self, artifact_id: str | None) -> _HostedModel:
        with self._lock:
            aid = artifact_id or self._default_id
            if aid is None or aid not in self._models:
                raise KeyError(f"no hosted model {aid!r}; "
                               f"have {sorted(self._models)}")
            return self._models[aid]

    def unload(self, artifact_id: str) -> bool:
        """Drop a hosted model (its jit cache, caches, and key memo go with
        it).  In-flight predicts that already resolved the hosted entry
        finish on it; new requests for the id get KeyError.  Returns whether
        the id was hosted."""
        with self._lock:
            hosted = self._models.pop(artifact_id, None)
            if self._default_id == artifact_id:
                self._default_id = min(self._models, default=None)
        return hosted is not None

    @property
    def artifact_ids(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    # -- warm path ----------------------------------------------------------

    def _predict_padded(self, hosted: _HostedModel, x: np.ndarray):
        """Pad to the power-of-two bucket, run the jitted program, trim."""
        b = x.shape[0]
        bucket = padding_bucket(b, self.max_batch)
        ch = self._bucket_children.get(bucket)
        if ch is None:       # bind the labeled child once per padding bucket
            ch = self._bucket_children[bucket] = self._m_bucket.labels(bucket)
        ch.inc()
        xp = np.zeros((bucket, x.shape[1]), np.float32)
        xp[:b] = x
        out = hosted.predict_fn(hosted.loaded.model.tables, xp)
        return np.asarray(out)[:b]

    def _predict_warm(self, hosted: _HostedModel, x: np.ndarray):
        with self._lock:
            self._n_predicts += 1
            call_idx = self._n_predicts
        serve_fault(self.fault_plan, call_idx)
        with self._t_warm():
            chunks = [self._predict_padded(hosted, x[i:i + self.max_batch])
                      for i in range(0, x.shape[0], self.max_batch)]
            return chunks[0] if len(chunks) == 1 else np.concatenate(chunks)

    def predict(self, x, *, artifact_id: str | None = None,
                use_cache: bool = True, validate: bool = True) -> np.ndarray:
        """Serve a (d,) point or (b, d) batch.

        ``validate`` rejects non-finite query rows with ``InvalidRequest``
        BEFORE they reach the model — a NaN query must surface as a
        structured error, never as a silently-NaN prediction (and never as a
        poisoned cache entry served to later callers)."""
        try:
            with self._t_predict():
                return self._predict(x, artifact_id=artifact_id,
                                     use_cache=use_cache, validate=validate)
        except BaseException as e:
            with self._lock:
                self._n_errors += 1
                self._last_error = repr(e)
            self._m_errors.inc()
            raise

    def _predict(self, x, *, artifact_id, use_cache, validate) -> np.ndarray:
        hosted = self._hosted(artifact_id)
        with self._lock:
            self._n_requests += 1
        self._m_requests.inc()
        x = np.asarray(x, np.float32)
        single = x.ndim == 1
        if single:
            x = x[None, :]
        if validate and not np.isfinite(x).all():
            bad = np.flatnonzero(~np.isfinite(x).all(axis=1))
            raise InvalidRequest(
                f"non-finite query row(s) {bad[:8].tolist()} "
                f"({len(bad)} of {x.shape[0]})")
        if hosted.cache is None or not use_cache:
            out = self._predict_warm(hosted, x)
            return out[0] if single else out

        with self._t_probe():
            keys = self._bucket_keys(hosted, x)
            found = hosted.cache.get_many(keys)
        if single and found[0] is not None:       # all-hit serving fast path
            self._m_hits.inc()
            v = found[0]
            # hand out a copy, never the stored row: an in-place caller
            # mutation must not rewrite the cache (np scalars are immutable)
            return v.copy() if isinstance(v, np.ndarray) else v
        miss = [i for i, v in enumerate(found) if v is None]
        if len(found) > len(miss):
            self._m_hits.inc(len(found) - len(miss))
        if miss:
            self._m_misses.inc(len(miss))
            fresh = self._predict_warm(hosted, x[miss])
            hosted.cache.put_many([keys[i] for i in miss], list(fresh))
            for j, i in enumerate(miss):
                found[i] = fresh[j]
        out = np.stack(found)
        return out[0] if single else out

    def _bucket_keys(self, hosted: _HostedModel, x: np.ndarray) -> list[bytes]:
        """Bucket key per query row, through a raw-bytes -> key memo.

        The bucket key itself is deterministic in the raw row (normalization
        + hash pipeline are pure), so memoizing it is exact; a repeat query
        costs one ``tobytes`` and two dict probes instead of the ~12-op numpy
        hash — that gap is most of the cache path's >=10x over the warm path.
        Keys are computed on what the jit path actually featurizes: the
        NORMALIZED query (numpy f32 mirrors the jitted f32 normalization
        bitwise — both are IEEE sub/div).
        """
        raw = [row.tobytes() for row in x]
        memo = hosted.keymemo.get_many(raw)
        miss = [i for i, k in enumerate(memo) if k is None]
        if miss:
            norm = hosted.loaded.norm
            xm = x[miss]
            if norm is not None:
                xm = ((xm - np.asarray(norm.x_mean, np.float32))
                      / np.asarray(norm.x_std, np.float32)).astype(np.float32)
            fresh = hosted.keyfn(xm)
            hosted.keymemo.put_many([raw[i] for i in miss], fresh)
            for j, i in enumerate(miss):
                memo[i] = fresh[j]
        return memo

    # -- compile management -------------------------------------------------

    def warmup(self, *, artifact_id: str | None = None,
               sizes: tuple[int, ...] | None = None) -> int:
        """Pre-compile every padding bucket (or just ``sizes``' buckets) so
        the first real request never pays the compile.  Returns the jit cache
        size afterwards."""
        hosted = self._hosted(artifact_id)
        d = hosted.loaded.model.lsh.d
        buckets = sorted({padding_bucket(s, self.max_batch)
                          for s in (sizes or self._all_buckets())})
        for b in buckets:
            np.asarray(hosted.predict_fn(hosted.loaded.model.tables,
                                         np.zeros((b, d), np.float32)))
        return self.compile_count(artifact_id=artifact_id)

    def _all_buckets(self) -> list[int]:
        return [1 << p for p in range(self.max_batch.bit_length())]

    def compile_count(self, *, artifact_id: str | None = None) -> int:
        """Number of compiled entries in the hosted model's jit cache — the
        no-recompile-within-a-bucket property is pinned by asserting this
        stays flat across ragged request sizes."""
        return self._hosted(artifact_id).predict_fn._cache_size()

    def cache_stats(self, *, artifact_id: str | None = None) -> dict | None:
        hosted = self._hosted(artifact_id)
        return None if hosted.cache is None else hosted.cache.stats()

    def clear_cache(self, *, artifact_id: str | None = None) -> None:
        """Drop the model's cached predictions AND key memo (benchmark tier
        isolation; stats keep accumulating)."""
        hosted = self._hosted(artifact_id)
        if hosted.cache is not None:
            hosted.cache.clear()
        if hosted.keymemo is not None:
            hosted.keymemo.clear()

    # -- health -------------------------------------------------------------

    def attach_batcher(self, batcher) -> None:
        """Fold an attached MicroBatcher's stats into ``health()``."""
        self._batcher = batcher

    def health(self) -> dict:
        """One-call serving health snapshot: hosted models, request/error
        counters, last error, and — when a batcher is attached — its queue
        depth, shed rate, p99 and crash state.  Cheap enough to poll."""
        with self._lock:
            snap = {
                "models": sorted(self._models),
                "requests": self._n_requests,
                "warm_calls": self._n_predicts,
                "errors": self._n_errors,
                "last_error": self._last_error,
            }
        batcher = self._batcher
        if batcher is not None:
            b = batcher.stats()
            snap["batcher"] = {k: b[k] for k in
                               ("queue_depth", "queue_depth_hwm", "shed",
                                "shed_rate", "deadline_expired", "p99_us",
                                "crashed", "last_error")}
        snap["ok"] = bool(snap["models"]) and not (
            batcher is not None and snap["batcher"]["crashed"])
        return snap
