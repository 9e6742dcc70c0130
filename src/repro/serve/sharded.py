"""Sharded serving tier: multi-shard hash-join prediction behind the batcher.

``ShardedPredictor`` is the multi-device sibling of ``Predictor``: it hosts
models whose (m, B[, k]) bucket tables are SHARDED over a
(model_shards, data_shards) device mesh — the P(model, data) layout
``make_krr_step_hashjoin`` trains into and ``export_artifact_sharded``
ships — so models too big for one host still serve point predictions.

Per hosted model there is ONE jitted route→serve→readout program per
padding bucket, built on ``make_krr_predict_hashjoin``'s routing: queries
are padded to a power-of-two bucket (>= data_shards so every shard gets
rows), their (instance, slot) requests all_to_all to the owner shards, the
owners serve their table slices, and one value exchange + model psum
assembles the predictions.  The default is the factory's ``dedup=False``
interactive mode (raw requests on the wire — no layout sort, no routing
scatters, no overflow) which keeps warm p50 within a small factor of the
single-host path; ``dedup=True`` selects the training routing's
deduplicated wire for bulk scoring.  The wire payload is float32 here (not
the training default bf16): serving parity with the single-host path is
pinned bitwise on an unsharded (1x1) mesh and <= 1e-5 on sharded meshes
(collectives reassociate f32 sums), and a serving tier must not trade
accuracy for wire bytes it can afford at batch sizes.

The bucket-exact LRU cache (serve/cache.py) becomes PER-SHARD-AWARE: a
query's prediction depends only on the data shards its m slots touch
(owner = slot // spp), so the cache key folds in exactly that touch set
plus those shards' table-piece versions.  A hit skips the route/all_to_all
path entirely, and hot-swapping one shard's piece
(``bump_shard_version``) invalidates only the entries touching it.

Multi-model placement: several smaller models co-serve on one mesh by
assigning each a contiguous MODEL-AXIS row slice (``placement=(lo, hi)``);
each placement gets its own submesh, and ``health()`` reports per-shard
overflow counters (from the routing's dropped-bucket accounting, PR 7's
StepStats plumbing) next to the attached batcher's queue depth.
"""
from __future__ import annotations

import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..core.bucket_fns import get_bucket_fn
from ..core.distributed import KRRStepConfig, make_krr_predict_hashjoin
from ..errors import InvalidRequest
from .artifact import LoadedShardedArtifact, load_artifact_sharded
from .cache import BucketKeyFn, PredictionCache
from .predictor import DEFAULT_MAX_BATCH, padding_bucket

MODEL_AXIS = "model"
DATA_AXIS = "data"


def parse_mesh_shape(spec: str) -> tuple[int, int]:
    """'2x2' -> (2, 2): (model_shards, data_shards)."""
    try:
        mm, nd = spec.lower().split("x")
        shape = (int(mm), int(nd))
    except ValueError:
        raise ValueError(f"mesh spec must look like '2x2', got {spec!r}")
    if shape[0] <= 0 or shape[1] <= 0:
        raise ValueError(f"mesh shape must be positive, got {spec!r}")
    return shape


class _ShardedModel(NamedTuple):
    loaded: LoadedShardedArtifact
    placement: tuple[int, int]   # [lo, hi) model-axis rows of the host mesh
    submesh: Mesh
    predict_fn: object           # jitted (x, lsh, table) -> (yhat, dropped)
    lsh_dev: object              # LSHParams device_put P(model, None)
    table_dev: object            # (m, B[, k]) device_put P(model, data)
    keyfn: BucketKeyFn
    cache: PredictionCache | None
    keymemo: PredictionCache | None  # raw bytes -> (base key, touch tuple)
    shard_versions: np.ndarray   # (data_shards,) int64, bumped on hot-swap
    overflow: np.ndarray         # (data_shards,) int64 dropped-bucket counts


class ShardedPredictor:
    """Hosts sharded models on a (model_shards, data_shards) mesh and serves
    point predictions with the same API surface as ``Predictor`` (predict /
    warmup / compile_count / cache_stats / attach_batcher / health), so the
    MicroBatcher and launch/krr_serve.py front either interchangeably.
    """

    def __init__(self, *, mesh_shape: tuple[int, int] = (1, 1),
                 backend: str | None = None,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 cache_entries: int = 0,
                 cap_factor: float = 4.0,
                 dedup: bool = False,
                 devices=None):
        mm, nd = int(mesh_shape[0]), int(mesh_shape[1])
        if nd & (nd - 1):
            raise ValueError(f"data_shards must be a power of two, got {nd}")
        if max_batch & (max_batch - 1) or max_batch < nd:
            raise ValueError(f"max_batch must be a power of two >= "
                             f"data_shards, got {max_batch} vs {nd}")
        devices = list(devices if devices is not None else jax.devices())
        if mm * nd > len(devices):
            raise ValueError(f"mesh {mm}x{nd} needs {mm * nd} devices, "
                             f"have {len(devices)}")
        self.mesh_shape = (mm, nd)
        self._devices = np.asarray(devices[:mm * nd]).reshape(mm, nd)
        self.mesh = Mesh(self._devices, (MODEL_AXIS, DATA_AXIS))
        self.backend = backend
        self.max_batch = int(max_batch)
        self.cache_entries = int(cache_entries)
        # dedup=False is the interactive default: the broadcast route has no
        # layout sort / routing scatters, which at padded serving batches is
        # several times lower latency than the dedup pack (and can never
        # overflow).  dedup=True switches to the training routing's
        # deduplicated wire for bulk scoring; cap_factor then defaults to
        # headroom-first 4.0, 2x the training default (small batches
        # concentrate on few owners; overflow drops mass).
        self.dedup = bool(dedup)
        self.cap_factor = float(cap_factor)
        self._models: dict[str, _ShardedModel] = {}
        self._default_id: str | None = None
        self._lock = threading.Lock()
        self._n_requests = 0
        self._n_predicts = 0
        self._n_errors = 0
        self._last_error: str | None = None
        self._batcher = None
        # same metric families as the single-host Predictor — one schema
        # across serving tiers, aggregated in the shared registry
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
        # flat pre-bound timers (see Predictor): the per-request sites
        self._t_predict = obs.timer("serve.predict",
                                    to_histogram=self._m_predict_us)
        self._t_warm = obs.timer("serve.warm_compute",
                                 to_histogram=self._m_warm_us)
        self._t_probe = obs.timer("serve.cache_probe",
                                  to_histogram=self._m_probe_us)

    # -- model hosting ------------------------------------------------------

    def load(self, directory: str, *, artifact_id: str | None = None,
             placement: tuple[int, int] | None = None, retries: int = 0,
             retry_backoff_s: float = 0.05) -> str:
        """Load a sharded artifact and host it on model rows
        ``placement=[lo, hi)`` (default: the whole model axis).  The
        artifact must have been exported for exactly the
        (hi-lo, data_shards) grid — ``load_artifact_sharded`` refuses a
        mismatched manifest.  ``retries`` re-attempts transient piece/manifest
        read failures with exponential backoff (same contract as
        ``Predictor.load``)."""
        lo, hi = placement or (0, self.mesh_shape[0])
        loaded = load_artifact_sharded(
            directory, mesh_shape=(hi - lo, self.mesh_shape[1]),
            backend=self.backend, artifact_id=artifact_id, retries=retries,
            retry_backoff_s=retry_backoff_s)
        return self.add_model(loaded, placement=(lo, hi))

    def add_model(self, loaded: LoadedShardedArtifact, *,
                  placement: tuple[int, int] | None = None) -> str:
        mm, nd = self.mesh_shape
        lo, hi = placement or (0, mm)
        if not (0 <= lo < hi <= mm):
            raise ValueError(f"placement {lo, hi} outside model axis "
                             f"[0, {mm})")
        if loaded.mesh_shape != (hi - lo, nd):
            raise ValueError(f"artifact sharded for mesh "
                             f"{loaded.mesh_shape}, placement {lo, hi} on a "
                             f"{mm}x{nd} mesh wants {(hi - lo, nd)}")
        model = loaded.model
        if model.tables.shape[0] % (hi - lo):
            raise ValueError(f"m={model.tables.shape[0]} not divisible by "
                             f"placement span {hi - lo}")
        submesh = (self.mesh if (lo, hi) == (0, mm) else
                   Mesh(self._devices[lo:hi], (MODEL_AXIS, DATA_AXIS)))
        cfg = KRRStepConfig(
            m=int(model.tables.shape[0]), table_size=int(model.table_size),
            lam=0.0, cg_iters=0, data_axes=(DATA_AXIS,),
            model_axis=MODEL_AXIS,
            backend=self.backend or model.backend)
        f = get_bucket_fn(model.bucket_name)
        lsh_sharding = jax.tree.map(
            lambda _: NamedSharding(submesh, P(MODEL_AXIS, None)), model.lsh)
        table_sharding = NamedSharding(submesh, P(MODEL_AXIS, DATA_AXIS))
        # in_shardings lets the warm path hand the jit a HOST array: the
        # query's host->device split runs on the C++ dispatch path instead
        # of a per-call python device_put, which at serving batches is a
        # large fraction of end-to-end latency on small meshes
        predict_fn = jax.jit(
            make_krr_predict_hashjoin(
                submesh, cfg, f, cap_factor=self.cap_factor,
                payload_dtype=jnp.float32, with_stats=True,
                dedup=self.dedup),
            in_shardings=(NamedSharding(submesh, P(DATA_AXIS, None)),
                          lsh_sharding, table_sharding))
        lsh_dev = jax.device_put(model.lsh, lsh_sharding)
        table_dev = jax.device_put(model.tables, table_sharding)
        hosted = _ShardedModel(
            loaded=loaded, placement=(lo, hi), submesh=submesh,
            predict_fn=predict_fn, lsh_dev=lsh_dev, table_dev=table_dev,
            keyfn=BucketKeyFn(model.lsh, f),
            cache=(PredictionCache(self.cache_entries)
                   if self.cache_entries > 0 else None),
            keymemo=(PredictionCache(self.cache_entries)
                     if self.cache_entries > 0 else None),
            shard_versions=np.zeros(nd, np.int64),
            overflow=np.zeros(nd, np.int64))
        with self._lock:
            self._models[loaded.artifact_id] = hosted
            if self._default_id is None:
                self._default_id = loaded.artifact_id
        obs.counter("serve_models_loaded_total",
                    "artifacts hosted over the process lifetime").inc()
        if hosted.cache is not None:
            # same pull-time cache gauges as the single-host Predictor — a
            # sharded-only process must expose the full serving contract
            cache = hosted.cache
            obs.gauge("serve_cache_entries", "live prediction-cache entries",
                      labels=("model",)).labels(loaded.artifact_id).set_fn(
                lambda cache=cache: cache.stats()["entries"])
            obs.gauge("serve_cache_evictions",
                      "prediction-cache evictions to date",
                      labels=("model",)).labels(loaded.artifact_id).set_fn(
                lambda cache=cache: cache.stats()["evictions"])
        # per-shard pull-time gauges, registered at hosting time so the
        # series EXIST (at 0) even in broadcast mode where overflow is
        # structurally impossible — an absent series and a zero series mean
        # different things to an alerting rule
        ovf = obs.gauge("serve_shard_overflow_dropped",
                        "distinct buckets dropped past routing capacity, "
                        "per data shard", labels=("model", "shard"))
        ver = obs.gauge("serve_shard_piece_version",
                        "hot-swap version of each data shard's table piece",
                        labels=("model", "shard"))
        for j in range(nd):
            ovf.labels(loaded.artifact_id, j).set_fn(
                lambda h=hosted, j=j: int(h.overflow[j]))
            ver.labels(loaded.artifact_id, j).set_fn(
                lambda h=hosted, j=j: int(h.shard_versions[j]))
        return loaded.artifact_id

    def _hosted(self, artifact_id: str | None) -> _ShardedModel:
        with self._lock:
            aid = artifact_id or self._default_id
            if aid is None or aid not in self._models:
                raise KeyError(f"no hosted model {aid!r}; "
                               f"have {sorted(self._models)}")
            return self._models[aid]

    def unload(self, artifact_id: str) -> bool:
        """Drop a hosted model (jitted programs, device-placed tables,
        caches).  Same contract as ``Predictor.unload``."""
        with self._lock:
            hosted = self._models.pop(artifact_id, None)
            if self._default_id == artifact_id:
                self._default_id = min(self._models, default=None)
        return hosted is not None

    @property
    def artifact_ids(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def bump_shard_version(self, shard: int, *,
                           artifact_id: str | None = None) -> None:
        """Record that data shard ``shard``'s table piece changed (hot swap):
        cached entries whose slot set touches it stop matching, everything
        else keeps hitting."""
        hosted = self._hosted(artifact_id)
        if not 0 <= shard < self.mesh_shape[1]:
            raise ValueError(f"shard {shard} outside [0, "
                             f"{self.mesh_shape[1]})")
        with self._lock:
            hosted.shard_versions[shard] += 1

    # -- warm (sharded) path ------------------------------------------------

    def _bucket(self, n: int) -> int:
        # every data shard must receive rows: bucket >= data_shards
        return max(self.mesh_shape[1], padding_bucket(n, self.max_batch))

    def _predict_padded(self, hosted: _ShardedModel, x: np.ndarray):
        b = x.shape[0]
        bucket = self._bucket(b)
        ch = self._bucket_children.get(bucket)
        if ch is None:       # bind the labeled child once per padding bucket
            ch = self._bucket_children[bucket] = self._m_bucket.labels(bucket)
        ch.inc()
        if b == bucket and x.dtype == np.float32:
            xp = np.ascontiguousarray(x)   # already bucket-sized: no copy
        else:
            xp = np.zeros((bucket, x.shape[1]), np.float32)
            xp[:b] = x
        # host array straight in: in_shardings (add_model) places it
        out, dropped = hosted.predict_fn(xp, hosted.lsh_dev,
                                         hosted.table_dev)
        if self.dedup:
            # broadcast mode can't overflow (stats are structurally zero);
            # skipping the transfer keeps it off the warm critical path
            with self._lock:
                hosted.overflow[:] += np.asarray(dropped, np.int64)
        return np.asarray(out)[:b]

    def _predict_warm(self, hosted: _ShardedModel, x: np.ndarray):
        with self._lock:
            self._n_predicts += 1
        norm = hosted.loaded.norm
        with self._t_warm():
            if norm is not None:
                # host-side f32 normalization mirrors the single-host in-jit
                # one bitwise (both IEEE sub/div) — and matches the cache keys
                x = ((x - norm.x_mean) / norm.x_std).astype(np.float32)
            chunks = [self._predict_padded(hosted, x[i:i + self.max_batch])
                      for i in range(0, x.shape[0], self.max_batch)]
            out = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
            if norm is not None:
                out = (out * np.float32(norm.y_std)
                       + np.float32(norm.y_mean)).astype(out.dtype)
            return out

    def predict(self, x, *, artifact_id: str | None = None,
                use_cache: bool = True, validate: bool = True) -> np.ndarray:
        """Serve a (d,) point or (b, d) batch against the sharded table."""
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
            keys = self._sharded_keys(hosted, x)
            found = hosted.cache.get_many(keys)
        miss = [i for i, v in enumerate(found) if v is None]
        if len(found) > len(miss):
            self._m_hits.inc(len(found) - len(miss))
        if miss:
            self._m_misses.inc(len(miss))
            fresh = self._predict_warm(hosted, x[miss])
            hosted.cache.put_many([keys[i] for i in miss], list(fresh))
            for j, i in enumerate(miss):
                found[i] = fresh[j]
        out = np.stack([v.copy() if isinstance(v, np.ndarray) else v
                        for v in found])
        return out[0] if single else out

    def _sharded_keys(self, hosted: _ShardedModel, x: np.ndarray
                      ) -> list[bytes]:
        """Per-row sharded cache key: bucket key + the touched shards' ids
        AND current piece versions.  The (base key, touch set) pair is
        deterministic in the raw row, so it memoizes exactly (as in
        ``Predictor._bucket_keys``); the version suffix is applied per
        lookup so a ``bump_shard_version`` takes effect immediately."""
        raw = [row.tobytes() for row in x]
        memo = (hosted.keymemo.get_many(raw) if hosted.keymemo is not None
                else [None] * len(raw))
        miss = [i for i, k in enumerate(memo) if k is None]
        if miss:
            norm = hosted.loaded.norm
            xm = x[miss]
            if norm is not None:
                xm = ((xm - norm.x_mean) / norm.x_std).astype(np.float32)
            fresh = hosted.keyfn.keys_with_touch(
                xm, table_size=int(hosted.loaded.model.table_size),
                n_shards=self.mesh_shape[1])
            if hosted.keymemo is not None:
                hosted.keymemo.put_many([raw[i] for i in miss], fresh)
            for j, i in enumerate(miss):
                memo[i] = fresh[j]
        with self._lock:
            versions = hosted.shard_versions.copy()
        out = []
        for base, touched in memo:
            tv = np.asarray([(j, versions[j]) for j in touched], np.int64)
            out.append(base + b"|shards" + tv.tobytes())
        return out

    # -- compile management -------------------------------------------------

    def warmup(self, *, artifact_id: str | None = None,
               sizes: tuple[int, ...] | None = None) -> int:
        """Pre-compile every padding bucket's route→serve→readout program
        (sharded compiles are the expensive ones — they lower collectives),
        so the first real request never pays one."""
        hosted = self._hosted(artifact_id)
        d = hosted.loaded.model.lsh.d
        buckets = sorted({self._bucket(s) for s in
                          (sizes or self._all_buckets())})
        for b in buckets:
            self._predict_padded(hosted, np.zeros((b, d), np.float32))
        return self.compile_count(artifact_id=artifact_id)

    def _all_buckets(self) -> list[int]:
        return [1 << p for p in range(self.max_batch.bit_length())]

    def compile_count(self, *, artifact_id: str | None = None) -> int:
        return self._hosted(artifact_id).predict_fn._cache_size()

    def cache_stats(self, *, artifact_id: str | None = None) -> dict | None:
        hosted = self._hosted(artifact_id)
        return None if hosted.cache is None else hosted.cache.stats()

    def clear_cache(self, *, artifact_id: str | None = None) -> None:
        hosted = self._hosted(artifact_id)
        if hosted.cache is not None:
            hosted.cache.clear()
        if hosted.keymemo is not None:
            hosted.keymemo.clear()

    # -- health -------------------------------------------------------------

    def attach_batcher(self, batcher) -> None:
        self._batcher = batcher

    def health(self) -> dict:
        """Serving health incl. the sharded tier's observables: mesh shape,
        per-model placement + per-data-shard overflow counters (distinct
        buckets dropped past routing capacity — nonzero means cap_factor
        needs headroom) and piece versions, plus the attached batcher's
        queue depth."""
        with self._lock:
            snap = {
                "models": sorted(self._models),
                "mesh": {"model": self.mesh_shape[0],
                         "data": self.mesh_shape[1]},
                "requests": self._n_requests,
                "warm_calls": self._n_predicts,
                "errors": self._n_errors,
                "last_error": self._last_error,
                "shards": {
                    aid: {"placement": list(h.placement),
                          "overflow": h.overflow.tolist(),
                          "piece_versions": h.shard_versions.tolist()}
                    for aid, h in self._models.items()},
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
