"""Serving-path latency/throughput benchmark -> BENCH_serving.json.

Measures the three serving tiers the subsystem exists for, on one fitted
model (rect bucket, serving-scale m):

* **cold**   — a FRESH predictor's first single-query call, compile included:
  what a replica pays right after loading an artifact with no warmup.
* **warm**   — the steady-state single-query featurize+readout path (padding
  bucket already compiled, cache off): p50/p99 over many calls.
* **cached** — the same query answered by the bucket-exact cache (key memo +
  LRU probe, no jit entry): p50/p99.

plus the micro-batcher under several offered loads (paced submit loop ->
achieved QPS, latency percentiles, mean coalesced batch size), and a
**sharded** section: ShardedPredictor warm batch-``MAX_BATCH`` p50/p99 on a
fake-CPU 2x2 mesh, measured in a subprocess (the fake device count must be
set before jax initializes) TOGETHER with the single-host warm p50 at the
same batch in the same child, so ``ratio_vs_single`` compares like with
like.  That ratio is the sharded-serving acceptance pin (warm p50 within
3x of single-host) gated by ``check_regression --sharded``.

A **lifecycle** section measures the self-healing runtime (DESIGN.md §12):
single-query p50 before vs immediately after a live version swap
(``swap_p50_ratio``), the jit-cache growth across the swap
(``swap_compile_delta`` — pinned to 0 by ``check_regression --lifecycle``:
swaps must not recompile warm buckets), and forced-rollback
time-to-first-healthy-prediction (``rollback_to_healthy_us``).

The committed BENCH_serving.json is the regression baseline:
``benchmarks/check_regression.py`` gates warm_p50_us and cached_p50_us
against it (same platform only, machine-speed normalized via the shared
calibration workload).  The two structural claims — warm >= 5x faster than
cold, cache hit >= 10x faster than warm — are asserted by
tests/test_bench_regression.py --runslow off this module's ``run()``.

    PYTHONPATH=src python -m benchmarks.bench_serving [--json PATH] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile

import numpy as np

import jax

from repro import obs
from repro.serve import Predictor, bucket_sizes
from repro.serve.batcher import percentile

from .common import emit, refuse_on_tpu

# serving-scale model: m matches the quickstart fit; n only shapes the tables
MODEL_N = 2048
MODEL_D = 8
MODEL_M = 256
SEED = 0

OFFERED_QPS = (2000.0, 8000.0, 0.0)          # 0 = unthrottled
BATCH_REQUESTS = 2000
MAX_BATCH = 64
MAX_WAIT_US = 1000
DUP_FRAC = 0.5

SHARDED_MESH = (2, 2)                        # (model_shards, data_shards)


def _span_lat_us(fn, iters: int, span: str = "serve.predict"):
    """Sorted per-call latencies in us, read back from the predictor's own
    ``serve.predict`` spans — the benchmark reports the SAME samples the
    live /metrics histogram records, not a second ad-hoc clock."""
    obs.clear_span_samples(span)
    for _ in range(iters):
        fn()
    lat = obs.span_samples_us(span)
    assert len(lat) == iters, (len(lat), iters)
    return sorted(lat)


def run(*, iters: int = 300, batch_requests: int = BATCH_REQUESTS,
        offered_qps=OFFERED_QPS, repeats: int = 1) -> dict:
    """Returns the JSON-able result dict (stable schema: every key always
    present).  ``iters`` is the single-query sample count for the warm and
    cached percentiles; ``repeats`` re-runs only those measurement sections
    (min-of-N per percentile) so the regression gate can sample over minutes
    without re-paying the model fit / export / predictor compile."""
    from repro.launch.krr_serve import (_fit_and_export, _synthetic_stream,
                                        serve_stream)

    out = {"bench": "serving", "platform": jax.default_backend(),
           "model": {"n": MODEL_N, "d": MODEL_D, "m": MODEL_M},
           "max_batch": MAX_BATCH, "max_wait_us": MAX_WAIT_US,
           "dup_frac": DUP_FRAC}
    with tempfile.TemporaryDirectory() as tmp:
        art_dir = tmp + "/artifact"
        # one canonical serving fit, shared with the krr_serve selftest
        _fit_and_export(art_dir, n=MODEL_N, d=MODEL_D, m=MODEL_M, seed=SEED)
        q = (np.random.default_rng(SEED)
             .uniform(0.0, 2.0, size=(1, MODEL_D)).astype(np.float32))

        # cold: fresh predictor, first call pays tracing + compile
        cold_pred = Predictor(cache_entries=0)
        cold_pred.load(art_dir)
        out["cold_first_call_us"] = _span_lat_us(
            lambda: cold_pred.predict(q), 1)[0]

        # warm: steady-state single-query jit path (bucket compiled, no cache)
        pred = Predictor(cache_entries=65536)
        pred.load(art_dir)
        pred.warmup(sizes=bucket_sizes(MAX_BATCH))
        pred.predict(q)          # cached: first call inserts, later replay
        for key in ("warm_p50_us", "warm_p99_us",
                    "cached_p50_us", "cached_p99_us"):
            out[key] = float("inf")
        for _ in range(max(repeats, 1)):
            warm = _span_lat_us(lambda: pred.predict(q, use_cache=False),
                                iters)
            cached = _span_lat_us(lambda: pred.predict(q), iters)
            out["warm_p50_us"] = min(out["warm_p50_us"],
                                     percentile(warm, 50))
            out["warm_p99_us"] = min(out["warm_p99_us"],
                                     percentile(warm, 99))
            out["cached_p50_us"] = min(out["cached_p50_us"],
                                       percentile(cached, 50))
            out["cached_p99_us"] = min(out["cached_p99_us"],
                                       percentile(cached, 99))

        out["warm_speedup_vs_cold"] = \
            out["cold_first_call_us"] / out["warm_p50_us"]
        out["cache_speedup_vs_warm"] = \
            out["warm_p50_us"] / out["cached_p50_us"]

        # batcher tiers: same request stream at increasing offered load
        stream = _synthetic_stream(MODEL_D, batch_requests, DUP_FRAC,
                                   SEED + 1)
        rows = []
        for qps in offered_qps:
            # tier isolation: each offered load starts from a cold cache so
            # only the stream's own dup_frac produces hits
            pred.clear_cache()
            stats = serve_stream(pred, stream, max_batch=MAX_BATCH,
                                 max_wait_us=MAX_WAIT_US, target_qps=qps)
            rows.append({"offered_qps": qps or None,   # None = unthrottled
                         "achieved_qps": stats["qps"],
                         "p50_us": stats["p50_us"],
                         "p99_us": stats["p99_us"],
                         "mean_batch": stats["mean_batch"],
                         "batches": stats["batches"],
                         "requests": stats["served"]})
        out["batcher_rows"] = rows
    return out


# ---------------------------------------------------------------------------
# lifecycle section: swap disturbance + rollback time-to-healthy
# ---------------------------------------------------------------------------

def lifecycle_section(*, iters: int = 200, repeats: int = 3) -> dict:
    """Self-healing runtime costs (DESIGN.md §12), measured in-process:

    * ``steady_p50_us``    — single-query warm p50 through the runtime's
      version-resolving predict (the active-tuple read is the only cost the
      lifecycle layer adds to the predictor's own path);
    * ``post_swap_p50_us`` / ``swap_p50_ratio`` — the same measurement
      immediately after a live version swap: the disturbance pin (the
      candidate pre-warms before the flip, so the ratio should be ~1);
    * ``swap_compile_delta`` — jit-cache growth of the active model across
      the swap; MUST be 0 (a swap that recompiles warm buckets stalls every
      in-flight bucket on real accelerators);
    * ``rollback_to_healthy_us`` — forced rollback to the retained version
      through to the first healthy prediction, min over ``repeats``
      publish->swap->rollback cycles: the recovery-time budget.

    Failure yields an explicit ``{"error": ...}`` marker instead of raising,
    matching the sharded section's stable-schema contract.
    """
    try:
        return _lifecycle_measure(iters=iters, repeats=repeats)
    except Exception as e:  # noqa: BLE001 — marker, not silence
        return {"error": f"{type(e).__name__}: {e}"}


def _lifecycle_measure(*, iters: int, repeats: int) -> dict:
    from repro.launch.krr_serve import _fit
    from repro.serve import (LifecycleConfig, ServingRuntime,
                             export_artifact, version_dir)
    from time import perf_counter

    with tempfile.TemporaryDirectory() as tmp:
        root = tmp + "/versions"
        model, _ = _fit(n=MODEL_N, d=MODEL_D, m=MODEL_M, seed=SEED)
        export_artifact(version_dir(root, 1), model)
        cfg = LifecycleConfig(probation_s=0.0, retain=2, warm_sizes=(1,))
        rt = ServingRuntime(root, cache_entries=0, config=cfg)
        rt.poll_once()
        q = (np.random.default_rng(SEED)
             .uniform(0.0, 2.0, size=(1, MODEL_D)).astype(np.float32))
        rt.predict(q)
        res = {"steady_p50_us": float("inf"),
               "post_swap_p50_us": float("inf")}
        for _ in range(max(repeats, 1)):
            lat = _span_lat_us(lambda: rt.predict(q), iters)
            res["steady_p50_us"] = min(res["steady_p50_us"],
                                       percentile(lat, 50))
        c0 = rt.compile_count()
        export_artifact(version_dir(root, 2), model)
        report = rt.poll_once()
        assert report["action"] == "swap", report
        res["swap_compile_delta"] = rt.compile_count() - c0
        for _ in range(max(repeats, 1)):
            lat = _span_lat_us(lambda: rt.predict(q), iters)
            res["post_swap_p50_us"] = min(res["post_swap_p50_us"],
                                          percentile(lat, 50))
        res["swap_p50_ratio"] = (res["post_swap_p50_us"]
                                 / res["steady_p50_us"])
        heal = float("inf")
        ver = 2
        for _ in range(max(repeats, 1)):
            ver += 1
            export_artifact(version_dir(root, ver), model)
            report = rt.poll_once()
            assert report["action"] == "swap", report
            t0 = perf_counter()
            assert rt.rollback("bench: forced")
            rt.predict(q)        # first healthy answer post-rollback
            heal = min(heal, (perf_counter() - t0) * 1e6)
        res["rollback_to_healthy_us"] = heal
    return res


# ---------------------------------------------------------------------------
# sharded section: ShardedPredictor vs single-host warm path on a fake mesh
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import json, sys, tempfile
import numpy as np
import jax
from repro import obs
from repro.launch.krr_serve import _fit_and_export
from repro.serve import Predictor, ShardedPredictor
from repro.serve.batcher import percentile

mm, nd = (int(v) for v in sys.argv[1].split("x"))
iters, repeats, batch = (int(v) for v in sys.argv[2:5])
n, d, m = (int(v) for v in sys.argv[5:8])
assert len(jax.devices()) >= mm * nd, jax.devices()


def lat_us(fn, iters):
    # read the predictors' own serve.predict spans back instead of timing
    # around the call — same samples the /metrics histogram sees
    obs.clear_span_samples("serve.predict")
    for _ in range(iters):
        fn()
    return sorted(obs.span_samples_us("serve.predict"))


with tempfile.TemporaryDirectory() as tmp:
    # one fit, two exports: the single-host artifact is the same model, so
    # the latency ratio below is apples to apples
    model, _ = _fit_and_export(tmp + "/single", n=n, d=d, m=m, seed=0)
    _fit_and_export(tmp + "/sharded", n=n, d=d, m=m, seed=0,
                    mesh_shape=(mm, nd))
    single = Predictor(cache_entries=0)
    single.load(tmp + "/single")
    single.warmup(sizes=(batch,))
    sharded = ShardedPredictor(mesh_shape=(mm, nd), cache_entries=0)
    sharded.load(tmp + "/sharded")
    sharded.warmup(sizes=(batch,))
    q = (np.random.default_rng(0).uniform(0.0, 2.0, size=(batch, d))
         .astype(np.float32))
    res = {k: float("inf") for k in ("warm_p50_us", "warm_p99_us",
                                     "single_warm_p50_us")}
    for _ in range(repeats):
        s = lat_us(lambda: sharded.predict(q, use_cache=False), iters)
        u = lat_us(lambda: single.predict(q, use_cache=False), iters)
        res["warm_p50_us"] = min(res["warm_p50_us"], percentile(s, 50))
        res["warm_p99_us"] = min(res["warm_p99_us"], percentile(s, 99))
        res["single_warm_p50_us"] = min(res["single_warm_p50_us"],
                                        percentile(u, 50))
res["mesh"] = f"{mm}x{nd}"
res["batch"] = batch
res["ratio_vs_single"] = res["warm_p50_us"] / res["single_warm_p50_us"]
print("SHARDED:" + json.dumps(res))
"""


def sharded_section(*, mesh=SHARDED_MESH, iters: int = 100,
                    repeats: int = 3, batch: int = MAX_BATCH,
                    timeout: float = 900.0) -> dict:
    """Warm sharded-serving latencies at batch ``batch`` on a fake-CPU
    ``mesh``, measured in a subprocess (the fake device count must be set
    before jax initializes, which this process already did).  The child
    fits ONE model, serves it both ways, and reports sharded warm
    p50/p99 plus the single-host warm p50 from the same process —
    ``ratio_vs_single`` is the <=3x acceptance pin.  dedup=False broadcast
    wire (the ShardedPredictor interactive default).  Failure yields an
    explicit {"error": ...} marker instead of raising: a runner that cannot
    spawn fake devices says nothing about the code.  Refused on a TPU host
    (``refuse_on_tpu``)."""
    refuse_on_tpu("bench_serving.sharded_section")
    root = pathlib.Path(__file__).resolve().parent.parent
    need = mesh[0] * mesh[1]
    env = {"PYTHONPATH": str(root / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={need}"}
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_SCRIPT,
             f"{mesh[0]}x{mesh[1]}", str(iters), str(repeats), str(batch),
             str(MODEL_N), str(MODEL_D), str(MODEL_M)],
            env=env, capture_output=True, text=True, cwd=str(root),
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"mesh": f"{mesh[0]}x{mesh[1]}", "error": "timeout"}
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("SHARDED:")), None)
    if proc.returncode != 0 or line is None:
        return {"mesh": f"{mesh[0]}x{mesh[1]}",
                "error": (proc.stderr or "no output")[-500:]}
    return json.loads(line[len("SHARDED:"):])


def main(json_path: str | None = None, *, quick: bool = False) -> dict:
    from . import bench_matvec

    res = run(iters=100 if quick else 300,
              batch_requests=500 if quick else BATCH_REQUESTS,
              offered_qps=(0.0,) if quick else OFFERED_QPS)
    res["sharded"] = sharded_section(iters=50 if quick else 100,
                                     repeats=1 if quick else 3)
    res["lifecycle"] = lifecycle_section(iters=50 if quick else 200,
                                         repeats=1 if quick else 3)
    res["calib_us"] = bench_matvec.calibration_us()
    print(f"[bench_serving] cold first call {res['cold_first_call_us']:.0f}us "
          f"(compile included)")
    print(f"[bench_serving] warm single query p50 {res['warm_p50_us']:.0f}us "
          f"p99 {res['warm_p99_us']:.0f}us "
          f"({res['warm_speedup_vs_cold']:.0f}x vs cold)")
    print(f"[bench_serving] cached hit p50 {res['cached_p50_us']:.0f}us "
          f"p99 {res['cached_p99_us']:.0f}us "
          f"({res['cache_speedup_vs_warm']:.1f}x vs warm)")
    for row in res["batcher_rows"]:
        offered = ("unthrottled" if row["offered_qps"] is None
                   else f"{row['offered_qps']:.0f} offered")
        print(f"[bench_serving] batcher {offered}: "
              f"{row['achieved_qps']:.0f} QPS, p50 {row['p50_us']:.0f}us "
              f"p99 {row['p99_us']:.0f}us, "
              f"mean batch {row['mean_batch']:.1f}")
    sh = res["sharded"]
    if "error" in sh:
        print(f"[bench_serving] sharded {sh.get('mesh', '?')}: measurement "
              f"FAILED {sh['error'][:120]}")
    else:
        print(f"[bench_serving] sharded {sh['mesh']} batch {sh['batch']}: "
              f"warm p50 {sh['warm_p50_us']:.0f}us "
              f"p99 {sh['warm_p99_us']:.0f}us "
              f"({sh['ratio_vs_single']:.2f}x single-host warm "
              f"{sh['single_warm_p50_us']:.0f}us)")
    lc = res["lifecycle"]
    if "error" in lc:
        print(f"[bench_serving] lifecycle: measurement FAILED "
              f"{lc['error'][:120]}")
    else:
        print(f"[bench_serving] lifecycle: steady p50 "
              f"{lc['steady_p50_us']:.0f}us, post-swap p50 "
              f"{lc['post_swap_p50_us']:.0f}us "
              f"(ratio {lc['swap_p50_ratio']:.2f}, "
              f"compile delta {lc['swap_compile_delta']}), "
              f"rollback-to-healthy {lc['rollback_to_healthy_us']:.0f}us")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"[bench_serving] wrote {json_path}")
    emit("bench_serving", res["warm_p50_us"] * 1e-6,
         f"cache_speedup={res['cache_speedup_vs_warm']:.1f}x "
         f"warm_speedup_vs_cold={res['warm_speedup_vs_cold']:.0f}x")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_serving.json")
    ap.add_argument("--quick", action="store_true",
                    help="fewer samples + one batcher tier (CI artifact run)")
    args = ap.parse_args()
    main(args.json, quick=args.quick)
