"""Online KRR serving driver: load an exported artifact, serve a request
stream through the micro-batcher, report latency/QPS/cache stats.

    # export first (examples/quickstart.py --export /tmp/krr_artifact), then:
    PYTHONPATH=src python -m repro.launch.krr_serve --artifact /tmp/krr_artifact \
        --requests 2000 --dup-frac 0.5

    # self-contained smoke (fit -> export -> serve -> verify; used by CI):
    PYTHONPATH=src python -m repro.launch.krr_serve --selftest

    # live observability: Prometheus /metrics + JSON /healthz on a local
    # port (DESIGN.md §11); --metrics-dump appends a JSONL snapshot on exit:
    PYTHONPATH=src python -m repro.launch.krr_serve --selftest \
        --metrics-port 9100 --metrics-dump /tmp/krr_metrics.jsonl

    # SHARDED serving on a (model x data) device mesh (table pieces sharded
    # P(model, data), hash-join routing — DESIGN.md §10); 4 fake CPU devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m repro.launch.krr_serve --selftest --mesh 2x2

The request stream is synthetic by default (uniform points in the training
box, with ``--dup-frac`` of requests replaying earlier queries — that is the
traffic the bucket-exact cache exists for) or file-driven via ``--input``
pointing at an (n, d) ``.npy``.  Every request goes through submit -> coalesce
-> padded warm path (or cache hit) -> future, i.e. the exact production path.
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import tempfile
import time

import numpy as np

from .. import obs
from ..compile_cache import use_compile_cache
from ..serve import (DeadlineExceeded, LifecycleConfig, MicroBatcher,
                     Overloaded, Predictor, ServingRuntime, ShardedPredictor,
                     WorkerCrashed, bucket_sizes, parse_mesh_shape,
                     version_dir)

# series the live endpoint must expose once the selftest traffic has run —
# the CI serving job scrapes /metrics and fails if any are absent
_REQUIRED_SERIES = (
    "serve_requests_total", "serve_predict_us", "serve_warm_compute_us",
    "serve_padding_bucket_total", "serve_cache_hits_total",
    "serve_cache_misses_total", "serve_cache_entries",
    "serve_models_loaded_total", "serve_batcher_requests_total",
    "serve_batcher_served_total", "serve_queue_wait_us", "serve_batch_size",
    "serve_batch_fill_us", "serve_batch_predict_us", "serve_queue_depth_hwm",
)
# extra series that must exist under --mesh (registered per shard at load,
# so an alerting rule can tell "zero overflow" from "not sharded")
_SHARDED_SERIES = ("serve_shard_overflow_dropped", "serve_shard_piece_version")
# extra series under --watch: every lifecycle transition and breaker state
# change must be scrapeable, or the self-healing loop is invisible to ops
_LIFECYCLE_SERIES = (
    "lifecycle_reloads_total", "lifecycle_canary_total",
    "lifecycle_swaps_total", "lifecycle_rollbacks_total",
    "lifecycle_rollback_exhausted_total", "lifecycle_probation_total",
    "lifecycle_nonfinite_predictions_total", "lifecycle_active_version",
    "lifecycle_versions_retained", "lifecycle_worker_crashes_total",
    "lifecycle_worker_restarts_total", "breaker_state",
    "breaker_transitions_total", "breaker_rejections_total",
)


def _verify_metrics(url: str, predictor, *, sharded: bool,
                    lifecycle: bool = False) -> str | None:
    """Scrape the live endpoint and check the contract: every required
    series present on /metrics, /healthz green with the predictor component.
    Returns an error string, or None when the endpoint checks out."""
    import json
    import urllib.request

    obs.add_health_provider("predictor", predictor.health)
    try:
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        need = (_REQUIRED_SERIES + (_SHARDED_SERIES if sharded else ())
                + (_LIFECYCLE_SERIES if lifecycle else ()))
        missing = [n for n in need if f"# TYPE {n} " not in text]
        if missing:
            return f"/metrics missing series: {missing}"
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
            doc = json.loads(resp.read().decode())
        if doc.get("status") != "ok":
            return f"/healthz degraded: {doc}"
        if "predictor" not in doc.get("components", {}):
            return "/healthz missing the predictor component"
        return None
    finally:
        obs.remove_health_provider("predictor")


def _synthetic_stream(d: int, n_requests: int, dup_frac: float,
                      seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    fresh = (rng.uniform(0.0, 2.0, size=(n_requests, d))
             .astype(np.float32))
    out = fresh.copy()
    # the first request can never be a replay, so a "fraction" of 1.0 means
    # every row after it
    n_dup = min(int(dup_frac * n_requests), max(n_requests - 1, 0))
    if n_dup:
        # replay earlier rows: repeats arrive interleaved, like real traffic.
        # ascending order matters — processing position i only after every
        # j < i is final keeps each copied row actually present earlier in
        # the stream (unsorted, ~18% of the dups silently went unique)
        dup_pos = rng.choice(n_requests - 1, size=n_dup, replace=False) + 1
        for i in np.sort(dup_pos):
            out[i] = out[rng.integers(0, i)]
    return out


def serve_stream(predictor: Predictor, stream: np.ndarray, *,
                 max_batch: int, max_wait_us: int,
                 target_qps: float = 0.0, max_queue: int = 0,
                 deadline_us: int | None = None,
                 runtime: ServingRuntime | None = None) -> dict:
    """Push every row of ``stream`` through a MicroBatcher; returns the
    batcher stats plus end-to-end wall clock.  ``target_qps`` paces the
    offered load (0 = as fast as the submit loop goes).  Shed (Overloaded)
    and expired (DeadlineExceeded) requests are counted in
    ``stats['rejected']`` — degraded mode answers structurally, it never
    hangs or crashes the driver.  With ``runtime`` the stream runs through
    its SupervisedBatcher against the ACTIVE version instead (worker
    crashes restart, repeated failures trip the breaker; ``CircuitOpen``
    rejections count as shed)."""
    gap = 1.0 / target_qps if target_qps > 0 else 0.0
    kw = dict(max_batch=max_batch, max_wait_us=max_wait_us,
              dim=stream.shape[1], max_queue=max_queue,
              deadline_us=deadline_us)
    with (runtime.make_batcher(**kw) if runtime is not None else
          MicroBatcher(lambda xb: predictor.predict(xb), **kw)) as mb:
        predictor.attach_batcher(mb)
        t0 = time.perf_counter()
        futures = []
        for i, row in enumerate(stream):
            if gap:
                # sleep-based pacing: a busy-wait would pin the GIL and
                # starve the batcher's worker thread
                while True:
                    rem = t0 + i * gap - time.perf_counter()
                    if rem <= 0:
                        break
                    time.sleep(min(rem, 5e-4))
            futures.append(mb.submit(row))
        rows, rejected, crashed = [], 0, 0
        for f in futures:
            try:
                rows.append(f.result(timeout=60.0))
            except (Overloaded, DeadlineExceeded):
                rejected += 1
            except WorkerCrashed:
                crashed += 1    # supervised mode: the batch died, not the run
        wall = time.perf_counter() - t0
        stats = mb.stats()
    stats["wall_s"] = wall
    stats["offered_qps"] = target_qps or float("inf")
    stats["results"] = (np.stack(rows) if rows
                        else np.zeros((0,), np.float32))
    stats["rejected"] = rejected
    stats["crashed_requests"] = crashed
    return stats


def _fit(*, n: int = 1024, d: int = 8, m: int = 128, seed: int = 0):
    """Tiny in-process fit for --selftest and missing --artifact runs.
    Returns (model, x_train)."""
    import jax

    from ..core import WLSHKernelSpec, get_bucket_fn, wlsh_krr_fit

    key = jax.random.PRNGKey(seed)
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    model = wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec, m=m,
                         lam=0.5, backend="auto")
    return model, np.asarray(x, np.float32)


def _export(directory: str, model, *,
            mesh_shape: tuple[int, int] | None = None,
            artifact_id: str = "selftest") -> None:
    """Publish ``model`` flat or (``mesh_shape``) as a sharded piece grid."""
    from ..serve import export_artifact, export_artifact_sharded

    if mesh_shape is None:
        export_artifact(directory, model, artifact_id=artifact_id)
    else:
        export_artifact_sharded(directory, model, mesh_shape=mesh_shape,
                                artifact_id=artifact_id)


def _fit_and_export(directory: str, *, n: int = 1024, d: int = 8,
                    m: int = 128, seed: int = 0,
                    mesh_shape: tuple[int, int] | None = None):
    """``_fit`` + ``_export`` in one call.  Returns (model, x_train)."""
    model, x = _fit(n=n, d=d, m=m, seed=seed)
    _export(directory, model, mesh_shape=mesh_shape)
    return model, x


def selftest(metrics_url: str | None = None) -> int:
    """Export a small artifact, serve 100 requests through the in-process
    batcher, and verify every response against the library predict path —
    the CI serving smoke.  With ``metrics_url`` (set by --metrics-port) the
    selftest also scrapes its own live endpoint and fails if any required
    series is missing."""
    import jax.numpy as jnp

    from ..core import wlsh_krr_predict

    with tempfile.TemporaryDirectory() as tmp:
        model, xtr = _fit_and_export(tmp + "/artifact")
        predictor = Predictor(cache_entries=4096)
        predictor.load(tmp + "/artifact")
        predictor.warmup(sizes=bucket_sizes(16))
        stream = _synthetic_stream(xtr.shape[1], 100, dup_frac=0.3, seed=1)
        stats = serve_stream(predictor, stream, max_batch=16,
                             max_wait_us=1000)
        expect = np.asarray(wlsh_krr_predict(model, jnp.asarray(stream)))
        if stats["served"] != 100:
            print(f"[krr_serve] SELFTEST FAIL: served {stats['served']}/100")
            return 1
        # coalescing pads each micro-batch to its power-of-two bucket and XLA
        # tiles the instance-mean per shape, so cross-shape agreement is
        # ~1 ulp, not bitwise (bitwise is pinned per-path by tests)
        if not np.allclose(stats["results"], expect, atol=1e-6):
            print("[krr_serve] SELFTEST FAIL: batched serving != library "
                  "predictions")
            return 1
        # exactness of the serving path itself: replaying the same stream
        # must reproduce the first pass bit-for-bit (cache hits replay the
        # stored cold-path rows; repeated warm rows hit identical programs)
        replay = serve_stream(predictor, stream, max_batch=16,
                              max_wait_us=1000)
        if not np.array_equal(replay["results"], stats["results"]):
            print("[krr_serve] SELFTEST FAIL: replayed stream not bitwise "
                  "reproducible")
            return 1
        if metrics_url is not None:
            err = _verify_metrics(metrics_url, predictor, sharded=False)
            if err is not None:
                print(f"[krr_serve] SELFTEST FAIL: {err}")
                return 1
        cache = predictor.cache_stats()
        print(f"[krr_serve] selftest ok: 100/100 round-tripped (<=1e-6 of "
              f"the library path, replay bitwise); "
              f"{stats['batches']} batches (mean {stats['mean_batch']:.1f} "
              f"rows), p50 {stats['p50_us']:.0f}us p99 {stats['p99_us']:.0f}us, "
              f"cache hit rate {cache['hit_rate']:.2f}"
              + ("; metrics endpoint verified" if metrics_url else ""))
    return 0


def selftest_sharded(mesh_shape: tuple[int, int],
                     metrics_url: str | None = None) -> int:
    """Sharded-serving smoke for the serving-multidevice CI job: fit, export
    the piece grid, host it on a (model, data) mesh behind the batcher,
    serve 100 queries, and verify <=1e-5 against the single-host Predictor
    on the SAME model (plus a bitwise stream replay — cache hits and repeat
    warm rows must reproduce exactly whatever the mesh is)."""
    import jax

    from ..serve import Predictor, ShardedPredictor, export_artifact

    need = mesh_shape[0] * mesh_shape[1]
    if len(jax.devices()) < need:
        print(f"[krr_serve] SELFTEST FAIL: mesh "
              f"{mesh_shape[0]}x{mesh_shape[1]} needs {need} devices, have "
              f"{len(jax.devices())} (set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count={need})")
        return 1
    with tempfile.TemporaryDirectory() as tmp:
        model, xtr = _fit_and_export(tmp + "/sharded", mesh_shape=mesh_shape)
        export_artifact(tmp + "/single", model, artifact_id="selftest")
        single = Predictor(cache_entries=4096)
        single.load(tmp + "/single")
        predictor = ShardedPredictor(mesh_shape=mesh_shape,
                                     cache_entries=4096)
        predictor.load(tmp + "/sharded")
        n_compiled = predictor.warmup(sizes=bucket_sizes(16))
        stream = _synthetic_stream(xtr.shape[1], 100, dup_frac=0.3, seed=1)
        stats = serve_stream(predictor, stream, max_batch=16,
                             max_wait_us=1000)
        if stats["served"] != 100:
            print(f"[krr_serve] SELFTEST FAIL: served {stats['served']}/100")
            return 1
        expect = single.predict(stream, use_cache=False)
        err = float(np.abs(stats["results"] - expect).max())
        if err > 1e-5:
            print(f"[krr_serve] SELFTEST FAIL: sharded serving off the "
                  f"single-host path by {err:.2e} (> 1e-5)")
            return 1
        replay = serve_stream(predictor, stream, max_batch=16,
                              max_wait_us=1000)
        if not np.array_equal(replay["results"], stats["results"]):
            print("[krr_serve] SELFTEST FAIL: replayed stream not bitwise "
                  "reproducible")
            return 1
        health = predictor.health()
        overflow = health["shards"]["selftest"]["overflow"]
        if any(overflow):
            print(f"[krr_serve] SELFTEST FAIL: routing overflow dropped "
                  f"buckets: {overflow}")
            return 1
        if metrics_url is not None:
            merr = _verify_metrics(metrics_url, predictor, sharded=True)
            if merr is not None:
                print(f"[krr_serve] SELFTEST FAIL: {merr}")
                return 1
        cache = predictor.cache_stats()
        print(f"[krr_serve] sharded selftest ok "
              f"(mesh {mesh_shape[0]}x{mesh_shape[1]}): 100/100 within "
              f"{err:.1e} of single-host (replay bitwise, overflow 0); "
              f"{n_compiled} buckets compiled, {stats['batches']} batches, "
              f"p50 {stats['p50_us']:.0f}us p99 {stats['p99_us']:.0f}us, "
              f"cache hit rate {cache['hit_rate']:.2f}")
    return 0


def selftest_lifecycle(metrics_url: str | None = None,
                       mesh_shape: tuple[int, int] | None = None) -> int:
    """Self-healing smoke for the CI serving/chaos jobs (--selftest --watch).

    Drives the full recovery loop against a real version root: v1 serves a
    stream clean; a POISONED v2 (tables corrupted on disk after export) is
    canary-rejected with zero failed requests on v1; a good v3 swaps in
    mid-stream with no dropped request and no new compile on the warm
    buckets; a forced post-swap health regression auto-rolls back to v1
    (mesh variant: operator rollback — the sharded predictor has no fault
    plan); and a crashed batcher worker recovers through the breaker's
    half-open probe instead of staying dead.  With ``metrics_url`` the
    lifecycle_*/breaker_* series are asserted on the live endpoint.
    """
    import threading

    from ..errors import CircuitOpen, FaultInjected
    from ..testing.faults import (FaultPlan, crash_supervised_workers,
                                  poison_artifact_tables)

    if mesh_shape is not None:
        import jax
        need = mesh_shape[0] * mesh_shape[1]
        if len(jax.devices()) < need:
            print(f"[krr_serve] SELFTEST FAIL: mesh needs {need} devices, "
                  f"have {len(jax.devices())}")
            return 1
    with tempfile.TemporaryDirectory() as tmp:
        root = tmp + "/versions"
        model, xtr = _fit()
        d = xtr.shape[1]
        _export(version_dir(root, 1), model, mesh_shape=mesh_shape)
        cfg = LifecycleConfig(probation_s=30.0, probation_min_requests=20,
                              probation_max_error_rate=0.1, retain=2,
                              load_retries=2, warm_sizes=bucket_sizes(16))
        rt = ServingRuntime(root, mesh_shape=mesh_shape, cache_entries=4096,
                            config=cfg)
        r = rt.poll_once()
        if r["action"] != "swap" or rt.active_version != 1:
            print(f"[krr_serve] SELFTEST FAIL: v1 not adopted: {r}")
            return 1
        stream = _synthetic_stream(d, 100, dup_frac=0.3, seed=1)
        stats = serve_stream(rt.predictor, stream, max_batch=16,
                             max_wait_us=1000, runtime=rt)
        if stats["served"] != 100 or stats["rejected"] \
                or stats["crashed_requests"]:
            print(f"[krr_serve] SELFTEST FAIL: v1 stream "
                  f"{stats['served']}/100 served, "
                  f"{stats['rejected']} rejected")
            return 1
        base = stats["results"]
        c0 = rt.compile_count()

        # poisoned v2: published complete, then corrupted on disk — the
        # shape of damage only the canary catches (validation passes)
        _export(version_dir(root, 2), model, mesh_shape=mesh_shape)
        poison_artifact_tables(version_dir(root, 2), scale=3.0)
        r = rt.poll_once()
        if r["action"] != "canary_reject" or rt.active_version != 1:
            print(f"[krr_serve] SELFTEST FAIL: poisoned v2 not rejected: "
                  f"{r}")
            return 1
        stats = serve_stream(rt.predictor, stream, max_batch=16,
                             max_wait_us=1000, runtime=rt)
        if stats["served"] != 100 or stats["rejected"] \
                or stats["crashed_requests"] \
                or not np.array_equal(stats["results"], base):
            print("[krr_serve] SELFTEST FAIL: v1 service disturbed by the "
                  "rejected candidate")
            return 1

        # good v3: swap mid-stream — zero downtime, zero new compiles
        _export(version_dir(root, 3), model, mesh_shape=mesh_shape)
        swap_report = {}

        def mid_stream_poll():
            time.sleep(0.01)
            swap_report.update(rt.poll_once())

        poller = threading.Thread(target=mid_stream_poll)
        poller.start()
        stats = serve_stream(rt.predictor, stream, max_batch=16,
                             max_wait_us=1000, target_qps=2000.0, runtime=rt)
        poller.join()
        if swap_report.get("action") != "swap" or rt.active_version != 3:
            print(f"[krr_serve] SELFTEST FAIL: v3 not swapped mid-stream: "
                  f"{swap_report}")
            return 1
        if stats["served"] != 100 or stats["rejected"] \
                or stats["crashed_requests"]:
            print(f"[krr_serve] SELFTEST FAIL: swap dropped requests "
                  f"({stats['served']}/100)")
            return 1
        if not np.allclose(stats["results"], base, atol=1e-6):
            print("[krr_serve] SELFTEST FAIL: post-swap results diverged")
            return 1
        c1 = rt.compile_count()
        if c1 != c0:
            print(f"[krr_serve] SELFTEST FAIL: swap recompiled warm "
                  f"buckets ({c0} -> {c1})")
            return 1

        # forced health regression inside the probation window -> rollback
        if mesh_shape is None:
            rt.predictor.fault_plan = FaultPlan(serve_fail_every=1)
            probe = stream[:1]
            for _ in range(cfg.probation_min_requests * 3):
                try:
                    rt.predict(probe, use_cache=False)
                except FaultInjected:
                    pass
                if rt.active_version == 1:
                    break
            rt.predictor.fault_plan = None
            rolled = "auto"
        else:
            rt.rollback("forced regression (selftest)")
            rolled = "operator"
        if rt.active_version != 1:
            print(f"[krr_serve] SELFTEST FAIL: no rollback to v1 "
                  f"(active v{rt.active_version})")
            return 1
        out = rt.predict(np.asarray(stream[:4]), use_cache=False)
        if not np.allclose(out, base[:4], atol=1e-6):
            print("[krr_serve] SELFTEST FAIL: rolled-back v1 not serving")
            return 1

        # worker crash -> breaker opens -> half-open probe recovers
        sup = rt.make_batcher(failure_threshold=1, cooldown_s=0.2,
                              restart_backoff_s=0.01, max_batch=8,
                              max_wait_us=500, dim=d)
        try:
            sup.predict(stream[0], timeout=30.0)
            crash_supervised_workers(sup, crashes=1)
            try:
                sup.predict(stream[0], timeout=30.0)
                print("[krr_serve] SELFTEST FAIL: crashed worker answered")
                return 1
            except WorkerCrashed:
                pass
            try:
                sup.predict(stream[0], timeout=30.0)
                print("[krr_serve] SELFTEST FAIL: open breaker admitted")
                return 1
            except CircuitOpen:
                pass
            time.sleep(0.25)    # past the cooldown: half-open probe window
            out = sup.predict(stream[0], timeout=30.0)
            st = sup.stats()
            if st["breaker"]["state"] != "closed" or st["restarts"] != 1:
                print(f"[krr_serve] SELFTEST FAIL: breaker not recovered: "
                      f"{st['breaker']}, restarts {st['restarts']}")
                return 1
        finally:
            sup.close()
        if metrics_url is not None:
            err = _verify_metrics(metrics_url, rt,
                                  sharded=mesh_shape is not None,
                                  lifecycle=True)
            if err is not None:
                print(f"[krr_serve] SELFTEST FAIL: {err}")
                return 1
        h = rt.health()
        print(f"[krr_serve] lifecycle selftest ok"
              + (f" (mesh {mesh_shape[0]}x{mesh_shape[1]})"
                 if mesh_shape else "")
              + f": poisoned v2 canary-rejected with zero failed requests, "
              f"v3 swapped live (compiles {c0}->{c1}), {rolled} rollback "
              f"to v1, breaker reopened after worker crash; "
              f"rejected versions {h['rejected_versions']}"
              + ("; metrics endpoint verified" if metrics_url else ""))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", default=None,
                    help="artifact directory (from quickstart --export); "
                         "omitted -> fit+export a small model in-process")
    ap.add_argument("--selftest", action="store_true",
                    help="fit -> export -> serve 100 requests -> verify "
                         "bitwise (CI smoke); ignores the traffic flags")
    ap.add_argument("--watch", action="store_true",
                    help="treat --artifact as a VERSION ROOT (v1/, v2/, "
                         "...) and self-heal: poll for new versions, "
                         "canary-validate, swap atomically, auto-rollback "
                         "on post-swap regression, restart crashed batcher "
                         "workers behind a circuit breaker (with "
                         "--selftest: run the lifecycle chaos smoke)")
    ap.add_argument("--watch-interval", type=float, default=0.5,
                    metavar="S", help="version-poll cadence under --watch")
    ap.add_argument("--no-canary", action="store_true",
                    help="skip golden-query validation before a swap "
                         "(--watch; accepts any loadable version)")
    ap.add_argument("--rollback-window", type=float, default=5.0,
                    metavar="S",
                    help="probation: watch post-swap health for S seconds "
                         "and auto-rollback on regression (0 disables)")
    ap.add_argument("--retain", type=int, default=2,
                    help="previous versions kept hosted as rollback "
                         "targets under --watch")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "reference", "pallas"],
                    help="override the artifact's recorded backend")
    ap.add_argument("--input", default=None,
                    help=".npy of (n, d) request points (default: synthetic)")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--dup-frac", type=float, default=0.5,
                    help="fraction of synthetic requests replaying earlier "
                         "ones (the bucket-exact cache's traffic)")
    ap.add_argument("--target-qps", type=float, default=0.0,
                    help="paced offered load; 0 = unthrottled")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="load shedding: submits past this queue depth fail "
                         "fast with Overloaded (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline budget; a request still "
                         "queued past it fails with DeadlineExceeded "
                         "(0 = no deadline)")
    ap.add_argument("--cache-entries", type=int, default=65536,
                    help="bucket-exact cache size; 0 disables")
    ap.add_argument("--mesh", default=None, metavar="MxN",
                    help="serve SHARDED on a (model_shards M x data_shards "
                         "N) device mesh, e.g. --mesh 2x2; the artifact "
                         "must be a matching export_artifact_sharded piece "
                         "grid (omitted -> single-host Predictor)")
    ap.add_argument("--placement", default=None, metavar="LO:HI",
                    help="host the model on model-axis rows [LO, HI) of the "
                         "--mesh so several models co-serve (default: the "
                         "whole model axis)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="expose /metrics (Prometheus text) + /healthz on "
                         "127.0.0.1:PORT for the lifetime of the run "
                         "(0 = OS-picked port, printed at startup)")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="append a JSONL metrics snapshot to PATH on exit "
                         "(headless runs: scrape-free flight recorder)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    mesh_shape = parse_mesh_shape(args.mesh) if args.mesh else None
    server = None
    if args.metrics_port is not None:
        server = obs.serve_metrics(args.metrics_port)
        print(f"[krr_serve] metrics: {server.url}/metrics  "
              f"health: {server.url}/healthz")
    try:
        rc = _dispatch(args, mesh_shape, server)
    finally:
        if args.metrics_dump:
            obs.REGISTRY.write_jsonl(args.metrics_dump,
                                     extra={"driver": "krr_serve"})
            print(f"[krr_serve] metrics snapshot -> {args.metrics_dump}")
        if server is not None:
            server.close()
    return rc


def _dispatch(args, mesh_shape, server) -> int:
    if args.selftest:
        url = server.url if server is not None else None
        if args.watch:
            return selftest_lifecycle(metrics_url=url, mesh_shape=mesh_shape)
        return (selftest_sharded(mesh_shape, metrics_url=url)
                if mesh_shape else selftest(metrics_url=url))
    if args.watch:
        return _watch_main(args, mesh_shape, server)

    placement = None
    if args.placement:
        lo, hi = args.placement.split(":")
        placement = (int(lo), int(hi))
    if mesh_shape is not None:
        predictor = ShardedPredictor(mesh_shape=mesh_shape,
                                     backend=args.backend,
                                     cache_entries=args.cache_entries)
    else:
        predictor = Predictor(backend=args.backend,
                              cache_entries=args.cache_entries)
    if server is not None:
        obs.add_health_provider("predictor", predictor.health)
    with contextlib.ExitStack() as stack:
        if args.artifact:
            aid = (predictor.load(args.artifact, placement=placement)
                   if mesh_shape is not None else
                   predictor.load(args.artifact))
        else:
            # demo artifact lives only for this run — cleaned up on exit
            tmp = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="krr_serve_"))
            print(f"[krr_serve] no --artifact: fitting a demo model "
                  f"-> {tmp}/artifact")
            span = ((placement[1] - placement[0], mesh_shape[1])
                    if mesh_shape and placement else mesh_shape)
            _fit_and_export(tmp + "/artifact", mesh_shape=span)
            aid = (predictor.load(tmp + "/artifact", placement=placement)
                   if mesh_shape is not None else
                   predictor.load(tmp + "/artifact"))
        return _serve_main(predictor, aid, args)


def _watch_main(args, mesh_shape, server) -> int:
    """--watch without --selftest: host a version root with the live
    watcher running (reload/canary/rollback on a daemon thread), serve the
    synthetic/file stream through the supervised batcher, report lifecycle
    health.  Publish a new ``v<N>`` under the root while this runs and it
    swaps in live (see the README runbook)."""
    cfg = LifecycleConfig(poll_interval_s=args.watch_interval,
                          canary_enabled=not args.no_canary,
                          probation_s=args.rollback_window,
                          retain=args.retain, load_retries=2,
                          warm_sizes=bucket_sizes(args.max_batch))
    with contextlib.ExitStack() as stack:
        root = args.artifact
        if root is None:
            tmp = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="krr_serve_"))
            root = tmp + "/versions"
            print(f"[krr_serve] no --artifact: fitting a demo model "
                  f"-> {version_dir(root, 1)}")
            model, _ = _fit()
            _export(version_dir(root, 1), model, mesh_shape=mesh_shape)
        rt = ServingRuntime(root, mesh_shape=mesh_shape,
                            backend=args.backend,
                            cache_entries=args.cache_entries, config=cfg)
        if server is not None:
            obs.add_health_provider("lifecycle", rt.health)
            stack.callback(obs.remove_health_provider, "lifecycle")
        rt.poll_once()
        if rt.active_version is None:
            print(f"[krr_serve] no published version under {root} "
                  f"(expected {version_dir(root, 1)} etc.)",
                  file=sys.stderr)
            return 2
        rt.start()
        stack.callback(rt.stop)
        d = rt._hosted().loaded.model.lsh.d
        print(f"[krr_serve] watching {root}: serving v{rt.active_version} "
              f"(poll every {cfg.poll_interval_s}s, canary "
              f"{'on' if cfg.canary_enabled else 'OFF'}, rollback window "
              f"{cfg.probation_s}s, retain {cfg.retain})")
        if args.input:
            stream = np.load(args.input).astype(np.float32)
            if stream.ndim != 2 or stream.shape[1] != d:
                print(f"[krr_serve] --input must be (n, {d}), "
                      f"got {stream.shape}", file=sys.stderr)
                return 2
        else:
            stream = _synthetic_stream(d, args.requests, args.dup_frac,
                                       args.seed)
        stats = serve_stream(rt.predictor, stream, max_batch=args.max_batch,
                             max_wait_us=args.max_wait_us,
                             target_qps=args.target_qps,
                             max_queue=args.max_queue,
                             deadline_us=(int(args.deadline_ms * 1000)
                                          if args.deadline_ms > 0 else None),
                             runtime=rt)
        h = rt.health()
        print(f"[krr_serve] {stats['served']} requests in "
              f"{stats['wall_s']:.2f}s -> {stats['qps']:.0f} QPS "
              f"(p50 {stats['p50_us']:.0f}us p99 {stats['p99_us']:.0f}us, "
              f"{stats['crashes']} worker crashes / {stats['restarts']} "
              f"restarts, breaker {stats['breaker']['state']})")
        print(f"[krr_serve] lifecycle: active v{h['active_version']}, "
              f"retained {h['retained_versions']}, rejected "
              f"{h['rejected_versions']}, ok={h['ok']}")
        return 0


def _serve_main(predictor: Predictor, aid: str, args) -> int:
    d = predictor._hosted(aid).loaded.model.lsh.d
    n_compiled = predictor.warmup(artifact_id=aid,
                                  sizes=bucket_sizes(args.max_batch))
    print(f"[krr_serve] hosting {aid!r} (d={d}, backend="
          f"{predictor._hosted(aid).loaded.operator.backend}); "
          f"{n_compiled} padding buckets compiled")

    if args.input:
        stream = np.load(args.input).astype(np.float32)
        if stream.ndim != 2 or stream.shape[1] != d:
            print(f"[krr_serve] --input must be (n, {d}), "
                  f"got {stream.shape}", file=sys.stderr)
            return 2
    else:
        stream = _synthetic_stream(d, args.requests, args.dup_frac, args.seed)

    stats = serve_stream(predictor, stream, max_batch=args.max_batch,
                         max_wait_us=args.max_wait_us,
                         target_qps=args.target_qps,
                         max_queue=args.max_queue,
                         deadline_us=(int(args.deadline_ms * 1000)
                                      if args.deadline_ms > 0 else None))
    print(f"[krr_serve] {stats['served']} requests in {stats['wall_s']:.2f}s "
          f"-> {stats['qps']:.0f} QPS achieved "
          f"({stats['batches']} batches, mean {stats['mean_batch']:.1f} "
          f"rows/batch)")
    print(f"[krr_serve] latency p50 {stats['p50_us']:.0f}us  "
          f"p99 {stats['p99_us']:.0f}us  (max_batch={args.max_batch}, "
          f"max_wait={args.max_wait_us}us)")
    if stats["rejected"]:
        print(f"[krr_serve] degraded mode: {stats['shed']} shed, "
              f"{stats['deadline_expired']} deadline-expired "
              f"({stats['rejected']} rejected total, shed rate "
              f"{stats['shed_rate']:.2f})")
    cache = predictor.cache_stats(artifact_id=aid)
    if cache is not None:
        print(f"[krr_serve] cache: {cache['entries']} entries, "
              f"hit rate {cache['hit_rate']:.2f} "
              f"({cache['hits']} hits / {cache['misses']} misses)")
    health = predictor.health()
    print(f"[krr_serve] health: ok={health['ok']} "
          f"requests={health['requests']} errors={health['errors']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
