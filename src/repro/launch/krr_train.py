"""Distributed WLSH-KRR driver — the paper's own workload on a jax mesh.

    PYTHONPATH=src python -m repro.launch.krr_train --dataset forest \
        --scale 0.01 --m 64 --lam 0.5

The mesh is whatever devices exist: the chips of a TPU host, or on the CPU
one device (XLA_FLAGS=--xla_force_host_platform_device_count=8 exercises the
collective paths).  On a real fleet the same code runs on the production
mesh — the step function is the one the multi-pod dry-run lowers
(launch/dryrun.py --cells krr).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from .. import obs
from ..compile_cache import use_compile_cache
from ..core.bucket_fns import get_bucket_fn
from ..core.distributed import (KRRStepConfig, OVERFLOW_POLICIES,
                                make_krr_predict, make_krr_predict_hashjoin,
                                make_krr_step, run_krr_step_resilient,
                                sample_sharded_lsh)
from ..core.precond import DEFAULT_NYSTROM_RANK
from ..core.lsh import GammaPDF
from ..data import make_regression_dataset
from ..backend import platform_of, resolve_backend
from .mesh import make_host_mesh

# hashjoin all_to_all payload dtypes (configs.wlsh_krr.wire_dtype mirrors)
WIRE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _pad_to(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths), n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="wine",
                    choices=["wine", "insurance", "ct_slices", "forest"])
    ap.add_argument("--scale", type=float, default=0.1,
                    help="dataset size fraction (CPU-friendly)")
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--bucket", default="rect", choices=["rect", "tent", "smooth"])
    ap.add_argument("--lengthscale", type=float, default=4.0)
    ap.add_argument("--cg-iters", type=int, default=50)
    ap.add_argument("--table-size", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="WLSH operator backend inside each shard "
                         "(auto = pallas on TPU, reference elsewhere)")
    ap.add_argument("--precond", default="none",
                    choices=["none", "jacobi", "nystrom"],
                    help="PCG preconditioner (core/precond.py): jacobi works "
                         "on any mesh; nystrom needs unsharded data axes "
                         "(single data shard) — it cuts ill-conditioned "
                         "(small --lam) iteration counts by >3x")
    ap.add_argument("--precond-rank", type=int, default=DEFAULT_NYSTROM_RANK,
                    help="Nyström pivot rank (ignored by none/jacobi)")
    ap.add_argument("--table-mode", default="psum",
                    choices=["psum", "hashjoin"],
                    help="bucket-table merge strategy: psum keeps the dense "
                         "(m, B) tables (paper-faithful); hashjoin shards "
                         "the table over the data axes and all_to_all-routes "
                         "only the nonzeros (DESIGN.md §6) — prediction "
                         "consumes the sharded table directly")
    ap.add_argument("--cap-factor", type=float, default=2.0,
                    help="hashjoin per-destination routing capacity factor "
                         "(cap ~ cap_factor·e/n_shards; overflow buckets "
                         "are dropped — tests pin the behavior)")
    ap.add_argument("--overflow", default="warn",
                    choices=list(OVERFLOW_POLICIES),
                    help="hashjoin capacity-overflow policy (DESIGN.md §9): "
                         "raise = fail the step with WireOverflowError, "
                         "warn = log and continue, allow = silent but still "
                         "counted")
    ap.add_argument("--wire-dtype", default="bf16",
                    choices=sorted(WIRE_DTYPES),
                    help="hashjoin all_to_all payload dtype: bf16 halves "
                         "the wire bytes (f32 accumulate, accuracy pinned); "
                         "f32 gives exact psum parity")
    ap.add_argument("--num-rhs", type=int, default=1,
                    help="solve an (n, k) RHS block: column 0 is y, the "
                         "rest are unit-normal probes — demonstrates the "
                         "multi-RHS matvec amortization (fit time is far "
                         "below k single solves)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the solve into DIR "
                         "(view with TensorBoard); also turns obs spans into "
                         "TraceAnnotations so fit/dist phases show up named "
                         "on the trace timeline")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="append a JSONL metrics snapshot to PATH on exit")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    xtr, ytr, xte, yte = make_regression_dataset(args.dataset, args.seed,
                                                 scale=args.scale)
    mesh = make_host_mesh()
    n_shards = mesh.devices.size
    xtr, n_tr = _pad_to(xtr, n_shards)
    ytr, _ = _pad_to(ytr, n_shards)          # padded rows: y=0 -> beta ~ 0
    xte_p, n_te = _pad_to(xte, n_shards)
    d = xtr.shape[1]
    table = args.table_size or (1 << max(10, (4 * xtr.shape[0] - 1).bit_length()))

    cfg = KRRStepConfig(m=args.m, table_size=table, lam=args.lam,
                        cg_iters=args.cg_iters, data_axes=("data",),
                        model_axis="model", backend=args.backend,
                        precond=args.precond,
                        precond_rank=args.precond_rank,
                        overflow=args.overflow)
    f = get_bucket_fn(args.bucket)
    lsh = sample_sharded_lsh(jax.random.PRNGKey(args.seed + 1), args.m, d,
                             GammaPDF(2.0, 1.0), args.lengthscale)

    if args.num_rhs > 1:
        # column 0 is the real target; the probe columns ride the same
        # matvecs/collectives, so fit time shows the block amortization
        probes = jax.random.normal(jax.random.PRNGKey(args.seed + 2),
                                   (ytr.shape[0], args.num_rhs - 1))
        ytr = jnp.concatenate([ytr[:, None], probes], axis=1)

    if args.trace_dir:
        obs.start_trace(args.trace_dir)
    if args.table_mode == "hashjoin":
        # the resilient runner applies --overflow to the step's fault
        # counters and retries a non-finite solve once on an f32 wire
        wire = WIRE_DTYPES[args.wire_dtype]
        predict = jax.jit(make_krr_predict_hashjoin(
            mesh, cfg, f, cap_factor=args.cap_factor, payload_dtype=wire))
        t0 = time.time()
        beta, resnorm, tables, stats = run_krr_step_resilient(
            mesh, cfg, f, xtr, ytr, lsh, cap_factor=args.cap_factor,
            payload_dtype=wire)
        jax.block_until_ready(beta)
        t_fit = time.time() - t0
        dropped = int(stats.overflow_dropped)
        if dropped:
            print(f"[krr] hashjoin dropped {dropped} bucket(s) past "
                  f"capacity (policy={args.overflow})")
    else:
        step = jax.jit(make_krr_step(mesh, cfg, f))
        predict = jax.jit(make_krr_predict(mesh, cfg, f))
        t0 = time.time()
        with obs.span("train.solve", {"table_mode": args.table_mode}):
            beta, resnorm, tables = step(xtr, ytr, lsh)
            jax.block_until_ready(beta)
        t_fit = time.time() - t0
    if args.trace_dir and obs.stop_trace():
        print(f"[krr] profiler trace -> {args.trace_dir} "
              f"(tensorboard --logdir {args.trace_dir})")
    yhat = predict(xte_p, lsh, tables)[:n_te]
    if args.num_rhs > 1:
        yhat, resnorm = yhat[:, 0], resnorm[0]
    rmse = float(jnp.sqrt(jnp.mean((yhat - yte) ** 2)))
    print(f"[krr] {args.dataset} scale={args.scale}: n={n_tr} d={d} "
          f"m={args.m} B={table} backend={args.backend} "
          f"({resolve_backend(cfg.backend, platform_of(mesh))}) "
          f"precond={args.precond} num_rhs={args.num_rhs} "
          f"table_mode={args.table_mode} wire={args.wire_dtype}")
    dev = mesh.devices.flat[0]
    print(f"[krr] fit {t_fit:.2f}s on {n_shards} shard(s) "
          f"({dev.platform} {dev.device_kind}); "
          f"CG residual {float(resnorm):.2e}; test RMSE {rmse:.4f} "
          f"(label std = 1.0)")
    _print_solve_metrics(args)
    if args.metrics_dump:
        obs.REGISTRY.write_jsonl(args.metrics_dump,
                                 extra={"driver": "krr_train",
                                        "dataset": args.dataset})
        print(f"[krr] metrics snapshot -> {args.metrics_dump}")
    return 0


def _print_solve_metrics(args) -> None:
    """Per-solve telemetry summary off the obs registry/spans — the same
    numbers /metrics would export, for headless runs with no scraper."""
    span = ("dist.krr_step" if args.table_mode == "hashjoin"
            else "train.solve")
    st = obs.span_stats(span)
    if st["count"]:
        print(f"[krr] obs: span {span} x{st['count']} "
              f"p50 {st['p50_us']/1e3:.1f}ms max {st['max_us']/1e3:.1f}ms")
    if args.table_mode == "hashjoin":
        snap = obs.REGISTRY.snapshot()

        def _val(name, default=0.0):
            fam = snap.get(name)
            if not fam or not fam.get("series"):
                return default
            return fam["series"][0].get("value", default)

        print(f"[krr] obs: hashjoin routing builds "
              f"{_val('hashjoin_routing_builds_total'):.0f}, route cap "
              f"{_val('hashjoin_route_cap'):.0f} (owner max "
              f"{_val('hashjoin_route_owner_max'):.0f}), a2a payload "
              f"{_val('hashjoin_a2a_payload_bytes')/1e6:.2f} MB, overflow "
              f"dropped {_val('hashjoin_overflow_dropped_total'):.0f}, wire "
              f"retries {_val('dist_wire_retry_total'):.0f}")


if __name__ == "__main__":
    raise SystemExit(main())
