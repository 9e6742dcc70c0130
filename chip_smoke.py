#!/usr/bin/env python3
"""Chip smoke: the WLSH-KRR main path on a TPU, end to end, in one process.

    python chip_smoke.py              # one chip: fit -> parity -> export -> serve
    python chip_smoke.py --chips 4    # four chips: data-sharded psum and
                                      # hash-join steps on a 4x1 mesh, 2x2
                                      # sharded serving, each against one chip

The shape is the paper's Forest Cover regression at its published size
(``make_regression_dataset("forest", scale=1.0)``: d=54, 500,000 training
and 81,012 test points, generated from ``--seed``), with m=64 instances, the
rect bucket and B = default_table_size(n) = 2^21 table slots.  Everything
runs through the entry points a user calls: ``wlsh_krr_fit`` /
``wlsh_krr_predict``, ``export_artifact`` -> ``Predictor`` behind a
``MicroBatcher``, ``make_krr_step`` / ``make_krr_step_hashjoin`` and
``ShardedPredictor``.  Every phase checks its results against the
reference backend or the one-chip path at the tolerance it prints.

Timings printed along the way are smoke numbers, not benchmark numbers.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
any failed check, a platform other than TPU, or ``REPRO_WLSH_BACKEND`` set
in the environment exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DATASET = "forest"
M = 64
# ridge lam = LAM_PER_POINT * n: regularization that scales with n, as
# large-scale KRR solvers use, keeps (K~ + lam I) conditioned alike at every
# n, so PCG converges well inside the iteration cap (~26 iterations at
# n=8,000, reference backend, CPU)
LAM_PER_POINT = 1e-3
SLICE_N = 65_536                # reference-backend fit slice (both backends)
MAXITER = 100                   # PCG iteration cap of the one-chip fits
CG_ITERS = 20                   # fixed CG iterations of the four-chip steps
FEATURIZE_CHECK_N = 16_384      # points whose hashes are compared bitwise
SERVE_REQUESTS = 300
SERVE_MAX_BATCH = 64

# tolerances, each checked against the quantity printed beside it
HASH_FLIP_FRAC = 1e-5           # featurize (instance, point) hashes allowed
                                # to differ from the reference
PREDICT_ATOL = 1e-5             # pallas vs reference readout, same tables:
                                # every test point (the gather is exact)
SLICE_REL_L2 = 1e-3             # pallas vs reference slice fit, predictions
SERVE_ATOL = 1e-6               # served vs library predict: ~1 ulp (DESIGN §8)
PSUM_ATOL = 1e-5                # 4x1 psum step vs the 1x1 step, beta
HASHJOIN_REL_L2 = 1e-2          # 4x1 hash-join step (bf16 wire) vs 1x1
SHARDED_ATOL = 1e-5             # 2x2 ShardedPredictor vs Predictor


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    log(("PASS " if ok else "FAIL ") + what)
    if not ok:
        raise SmokeFailure(what)


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def lengthscale(x: np.ndarray) -> float:
    """Median-heuristic L1 lengthscale (benchmarks/table2_krr.py): half the
    median pairwise L1 distance of a 256-point subsample."""
    xs = x[np.random.default_rng(0).choice(x.shape[0], 256, replace=False)]
    return float(np.median(np.abs(xs[:, None] - xs[None]).sum(-1))) / 2.0


def load_data(seed: int, device):
    import jax
    from repro.data import make_regression_dataset
    xtr, ytr, xte, yte = make_regression_dataset(DATASET, seed, scale=1.0)
    return jax.device_put((xtr, ytr, xte, yte), device)


# -- one chip ----------------------------------------------------------------

def phase_fit(args, device, data):
    """(a) wlsh_krr_fit at the Forest shape on one chip."""
    import jax
    import jax.numpy as jnp
    from repro.backend import resolve_backend
    from repro.core import WLSHKernelSpec, get_bucket_fn, wlsh_krr_fit
    from repro.core import wlsh_krr_predict
    xtr, ytr, xte, yte = data
    backend = resolve_backend("auto", device.platform)
    log(f"backend auto -> {backend}")
    check(backend == "pallas", "auto resolved to pallas on the chip")
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"),
                          lengthscale=lengthscale(np.asarray(xtr)))
    key = jax.random.PRNGKey(args.seed + 1)
    lam = LAM_PER_POINT * xtr.shape[0]
    fit = lambda: wlsh_krr_fit(key, xtr, ytr, spec, m=M, lam=lam,
                               maxiter=MAXITER)
    model, t_fit = timed(fit)
    log(f"fit: n={xtr.shape[0]} d={xtr.shape[1]} m={M} B={model.table_size} "
        f"lengthscale={spec.lengthscale:.3f} lam={lam:g}: "
        f"{int(model.cg_iters)} PCG iterations (maxiter {MAXITER}), "
        f"residual {float(model.cg_resnorm):.3e}, wall {t_fit:.1f}s "
        f"(first call, compiles included)")
    check(model.backend == "pallas", "model fitted on the pallas backend")
    check(model.tables.shape == (M, model.table_size)
          and bool(jnp.all(jnp.isfinite(model.tables))),
          f"tables finite, shape {tuple(model.tables.shape)}")
    yhat, t_pred = timed(wlsh_krr_predict, model, xte)
    rmse = float(jnp.sqrt(jnp.mean((yhat - yte) ** 2)))
    log(f"predict {xte.shape[0]} test points: {t_pred:.1f}s; "
        f"test RMSE {rmse:.4f} (labels standardized, std 1)")
    check(bool(np.isfinite(rmse)) and rmse < 1.0,
          f"test RMSE {rmse:.4f} below the predict-zero baseline 1.0")
    return model, spec, yhat


def phase_parity(args, device, data, model, spec, yhat):
    """(b) the same tables read out on the reference backend, featurize
    hashes against the reference, and a slice fit on both backends."""
    import jax
    import jax.numpy as jnp
    from repro.core import make_operator, wlsh_krr_fit, wlsh_krr_predict
    from repro.core.bucket_fns import get_bucket_fn
    xtr, ytr, xte, yte = data
    f = get_bucket_fn("rect")
    ops = {b: make_operator(model.lsh, f, model.table_size, backend=b,
                            platform=device.platform)
           for b in ("pallas", "reference")}
    xs = xte[:FEATURIZE_CHECK_N]
    fp, fr = (ops[b].featurize(xs) for b in ("pallas", "reference"))
    n_pairs = M * xs.shape[0]
    flips = int(jnp.sum((fp.key1 != fr.key1) | (fp.key2 != fr.key2)))
    log(f"featurize: {flips} of {n_pairs} (instance, point) hashes differ "
        f"from the reference; max |weight diff| "
        f"{float(jnp.max(jnp.abs(fp.weight - fr.weight))):.2e}")
    check(flips <= HASH_FLIP_FRAC * n_pairs,
          f"featurize hash mismatches {flips} <= {HASH_FLIP_FRAC:g} of pairs")

    yref, t_ref = timed(wlsh_krr_predict, model, xte, backend="reference")
    diff = np.abs(np.asarray(yhat) - np.asarray(yref))
    over = int(np.sum(diff > PREDICT_ATOL))
    log(f"readout parity on {xte.shape[0]} test points, same tables: "
        f"max |diff| {diff.max():.3e}, {over} past {PREDICT_ATOL:g}, "
        f"rel-L2 {rel_l2(yhat, yref):.3e} (reference predict {t_ref:.1f}s)")
    check(over == 0,
          f"pallas readout == reference within {PREDICT_ATOL:g} on every "
          f"test point")

    key = jax.random.PRNGKey(args.seed + 2)
    xs, ys = xtr[:SLICE_N], ytr[:SLICE_N]
    fits = {}
    for backend in ("pallas", "reference"):
        try:
            fits[backend], t = timed(
                lambda: wlsh_krr_fit(key, xs, ys, spec, m=M,
                                     lam=LAM_PER_POINT * SLICE_N,
                                     maxiter=MAXITER, backend=backend))
        except Exception as e:          # noqa: BLE001 — reported, then fails
            log(f"slice fit on {backend} raised {type(e).__name__}: {e}")
            raise
        log(f"slice fit n={SLICE_N} on {backend}: "
            f"{int(fits[backend].cg_iters)} iterations, residual "
            f"{float(fits[backend].cg_resnorm):.3e}, {t:.1f}s")
    yp = wlsh_krr_predict(fits["pallas"], xte)
    yr = wlsh_krr_predict(fits["reference"], xte, backend="reference")
    err = rel_l2(yp, yr)
    log(f"slice fit parity: test-prediction rel-L2 {err:.3e}, beta rel-L2 "
        f"{rel_l2(fits['pallas'].beta, fits['reference'].beta):.3e}")
    check(err < SLICE_REL_L2,
          f"slice fit pallas vs reference rel-L2 < {SLICE_REL_L2:g}")


def phase_serve(args, model, tmp):
    """(c) export_artifact -> Predictor -> MicroBatcher, checked against the
    library predict path."""
    import jax.numpy as jnp
    from repro.core import wlsh_krr_predict
    from repro.launch.krr_serve import _synthetic_stream, serve_stream
    from repro.serve import Predictor, bucket_sizes, export_artifact
    path = os.path.join(tmp, "artifact")
    _, t_exp = timed(export_artifact, path, model, artifact_id="forest")
    predictor = Predictor(cache_entries=4096)
    _, t_load = timed(predictor.load, path)
    t0 = time.perf_counter()
    predictor.warmup(sizes=bucket_sizes(SERVE_MAX_BATCH))
    t_warm = time.perf_counter() - t0
    log(f"export {t_exp:.1f}s, load {t_load:.1f}s, warmup of padding buckets "
        f"{bucket_sizes(SERVE_MAX_BATCH)} {t_warm:.1f}s")
    stream = _synthetic_stream(model.lsh.d, SERVE_REQUESTS, dup_frac=0.3,
                               seed=args.seed + 3)
    stats = serve_stream(predictor, stream, max_batch=SERVE_MAX_BATCH,
                         max_wait_us=2000)
    check(stats["served"] == SERVE_REQUESTS,
          f"served {stats['served']}/{SERVE_REQUESTS} requests")
    expect = np.asarray(wlsh_krr_predict(model, jnp.asarray(stream)))
    err = float(np.abs(stats["results"] - expect).max())
    log(f"serving: {stats['batches']} batches (mean {stats['mean_batch']:.1f} "
        f"rows), p50 {stats['p50_us'] / 1e3:.2f}ms p99 "
        f"{stats['p99_us'] / 1e3:.2f}ms, {stats['qps']:.0f} req/s, cache hit "
        f"rate {predictor.cache_stats()['hit_rate']:.2f}; max |served - "
        f"library| {err:.2e}")
    check(err <= SERVE_ATOL,
          f"every served answer within {SERVE_ATOL:g} of wlsh_krr_predict")


def run_one_chip(args, device) -> None:
    data, t_data = timed(load_data, args.seed, device)
    log(f"data: forest scale 1.0 generated on {device.device_kind} in "
        f"{t_data:.1f}s")
    model, spec, yhat = phase_fit(args, device, data)
    phase_parity(args, device, data, model, spec, yhat)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        phase_serve(args, model, tmp)


# -- four chips --------------------------------------------------------------

def run_four_chips(args, devices) -> None:
    """The data-sharded fit steps on a 4x1 mesh and 2x2 sharded serving,
    each against the same computation on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.core import get_bucket_fn
    from repro.core.distributed import (KRRStepConfig, make_krr_step,
                                        make_krr_step_hashjoin)
    from repro.core.krr import WLSHKRRModel
    from repro.core.lsh import GammaPDF, sample_lsh_params
    from repro.core.operator import default_table_size
    from repro.serve import (Predictor, ShardedPredictor, bucket_sizes,
                             export_artifact, export_artifact_sharded)
    from repro.launch.krr_serve import _synthetic_stream, serve_stream

    xtr, ytr, _, _ = load_data(args.seed, devices[0])
    n, d = xtr.shape
    f = get_bucket_fn("rect")
    table_size = default_table_size(n)
    lsh = sample_lsh_params(jax.random.PRNGKey(args.seed + 1), M, d,
                            GammaPDF(2.0, 1.0),
                            lengthscale(np.asarray(xtr)))
    cfg = KRRStepConfig(m=M, table_size=table_size, lam=LAM_PER_POINT * n,
                        cg_iters=CG_ITERS, data_axes=("data",),
                        model_axis="model")

    def place(mesh):
        data = NamedSharding(mesh, P("data", None))
        lsh_s = jax.tree.map(lambda _: NamedSharding(mesh, P("model", None)),
                             lsh)
        return (jax.device_put(xtr, data),
                jax.device_put(ytr, NamedSharding(mesh, P("data"))),
                jax.device_put(lsh, lsh_s))

    def distinct_devices(arr) -> int:
        return len({s.device for s in arr.addressable_shards})

    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    four = make_mesh((4, 1), ("data", "model"), devices=devices[:4])
    (beta1, res1, tables1), t1 = timed(jax.jit(make_krr_step(one, cfg, f)),
                                       *place(one))
    log(f"1x1 psum step: {CG_ITERS} CG iterations, residual "
        f"{float(res1):.3e}, {t1:.1f}s (compiles included)")
    args4 = place(four)
    check(all(distinct_devices(a) == 4 for a in args4[:2]),
          "training data sharded over 4 distinct devices")
    (beta4, res4, tables4), t4 = timed(jax.jit(make_krr_step(four, cfg, f)),
                                       *args4)
    check(distinct_devices(beta4) == 4, "4x1 beta on 4 distinct devices")
    err = float(jnp.max(jnp.abs(jax.device_put(beta4, devices[0]) - beta1)))
    log(f"4x1 psum step: residual {float(res4):.3e}, {t4:.1f}s; max |beta - "
        f"beta_1chip| {err:.3e}, tables rel-L2 "
        f"{rel_l2(tables4, tables1):.3e}")
    check(err <= PSUM_ATOL, f"psum step matches one chip within {PSUM_ATOL:g}")

    hj = jax.jit(make_krr_step_hashjoin(four, cfg, f))
    (beta_h, res_h, _, stats), t_h = timed(hj, *args4)
    check(distinct_devices(beta_h) == 4, "hash-join beta on 4 distinct devices")
    err_h = rel_l2(beta_h, beta1)
    log(f"4x1 hash-join step (bf16 wire): residual {float(res_h):.3e}, "
        f"{t_h:.1f}s, overflow dropped {int(stats.overflow_dropped)}; beta "
        f"rel-L2 vs one chip {err_h:.3e}")
    check(err_h < HASHJOIN_REL_L2,
          f"hash-join step rel-L2 < {HASHJOIN_REL_L2:g} vs one chip")

    model = WLSHKRRModel(lsh=lsh, bucket_name="rect", beta=beta1,
                         tables=tables1, table_size=table_size,
                         cg_iters=jnp.asarray(CG_ITERS),
                         cg_resnorm=res1, backend="pallas")
    stream = _synthetic_stream(d, SERVE_REQUESTS, dup_frac=0.3,
                               seed=args.seed + 3)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        export_artifact(tmp + "/flat", model, artifact_id="forest")
        export_artifact_sharded(tmp + "/sharded", model, mesh_shape=(2, 2),
                                artifact_id="forest")
        single = Predictor(max_batch=SERVE_MAX_BATCH)
        single.load(tmp + "/flat")
        sharded = ShardedPredictor(mesh_shape=(2, 2), devices=devices[:4],
                                   max_batch=SERVE_MAX_BATCH,
                                   cache_entries=4096)
        sharded.load(tmp + "/sharded")
        check(distinct_devices(sharded._hosted(None).table_dev) == 4,
              "2x2 sharded tables on 4 distinct devices")
        sharded.warmup(sizes=bucket_sizes(SERVE_MAX_BATCH))
        stats = serve_stream(sharded, stream, max_batch=SERVE_MAX_BATCH,
                             max_wait_us=2000)
        check(stats["served"] == SERVE_REQUESTS,
              f"sharded served {stats['served']}/{SERVE_REQUESTS} requests")
        expect = single.predict(stream, use_cache=False)
    err_s = float(np.abs(stats["results"] - expect).max())
    log(f"2x2 sharded serving: p50 {stats['p50_us'] / 1e3:.2f}ms p99 "
        f"{stats['p99_us'] / 1e3:.2f}ms; max |sharded - Predictor| "
        f"{err_s:.2e}")
    check(err_s <= SHARDED_ATOL,
          f"2x2 ShardedPredictor within {SHARDED_ATOL:g} of Predictor")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit/parity/serve on one chip; 4: the sharded "
                         "steps and sharded serving against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.backend import ENV_VAR
    if os.environ.get(ENV_VAR):
        print(f"chip_smoke: {ENV_VAR} is set; the smoke checks what "
              f"backend='auto' picks, so unset it", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; compile "
        f"cache {use_compile_cache()}; smoke numbers, not benchmark numbers")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args, devices)
        else:
            run_one_chip(args, dev)
    except SmokeFailure:
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
