"""Quickstart: approximate kernel ridge regression with WLSH estimators.

    PYTHONPATH=src python examples/quickstart.py [--backend auto|reference|pallas]
        [--precond none|jacobi|nystrom] [--num-rhs K]

Fits a Laplace-kernel GP sample with (a) exact KRR, (b) WLSH approximate KRR
(the paper's method), and compares accuracy and fit time.  ``--backend``
selects the WLSH operator implementation (see src/repro/core/operator.py):
'reference' is the pure-jnp path, 'pallas' the fused TPU kernels, 'auto'
picks per platform.  ``--precond`` runs the solve as preconditioned CG
(core/precond.py; 'nystrom' collapses the iteration count on
ill-conditioned, small-lam problems).  ``--num-rhs K`` with K > 1 draws
K - 1 GP posterior samples alongside the mean via pathwise conditioning —
one batched multi-RHS solve instead of K separate fits (core/gp.py).
Prediction streams through fixed-size batches — the same code path that
serves multi-million-point inference.

``--export DIR`` writes the fitted model as a serving artifact
(src/repro/serve/artifact.py) and ``--serve DIR`` loads it back through the
online Predictor and round-trips a request sample — the export -> serve hop
that `python -m repro.launch.krr_serve --artifact DIR` then runs at traffic.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (WLSHKernelSpec, exact_krr_fit, exact_krr_predict,
                        get_bucket_fn, laplace_kernel, wlsh_krr_fit,
                        wlsh_krr_predict)
from repro.core.gp import gp_posterior_rhs, gp_regression_dataset
from repro.core.precond import DEFAULT_NYSTROM_RANK


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"])
    ap.add_argument("--precond", default="none",
                    choices=["none", "jacobi", "nystrom"],
                    help="PCG preconditioner for the WLSH solve")
    ap.add_argument("--precond-rank", type=int, default=DEFAULT_NYSTROM_RANK)
    ap.add_argument("--num-rhs", type=int, default=1,
                    help="K > 1 adds K-1 pathwise GP posterior samples to "
                         "the solve as extra RHS columns (one batched fit)")
    ap.add_argument("--solve-checkpoint-dir", default=None, metavar="DIR",
                    help="persist the PCG SolveState under DIR during the "
                         "fit; re-running after a preemption resumes the "
                         "solve from the last saved chunk")
    ap.add_argument("--solve-checkpoint-every", type=int, default=0,
                    help="iterations between SolveState saves (0 with a "
                         "dir set = maxiter//10)")
    ap.add_argument("--export", default=None, metavar="DIR",
                    help="write the fitted WLSH model as a serving artifact")
    ap.add_argument("--serve", default=None, metavar="DIR",
                    help="load the artifact back through the online "
                         "Predictor and verify the round-trip (defaults to "
                         "the --export dir when both are wanted)")
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    n_train, n_test = 1200, 400
    noise = 0.05
    x, y, f_true = gp_regression_dataset(key, laplace_kernel,
                                         n=n_train + n_test, d=4, noise=noise)
    xtr, ytr = x[:n_train], y[:n_train]
    xte, fte = x[n_train:], f_true[n_train:]
    lam = 0.3

    t0 = time.time()
    beta = exact_krr_fit(laplace_kernel, xtr, ytr, lam)
    pred_exact = exact_krr_predict(laplace_kernel, xtr, beta, xte)
    t_exact = time.time() - t0
    rmse_exact = float(jnp.sqrt(jnp.mean((pred_exact - fte) ** 2)))

    # WLSH: f = rect + p(w) = w e^{-w}  <=>  the Laplace kernel (Def. 8)
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    n_samples = max(args.num_rhs - 1, 0)
    target = ytr
    f_prior = None
    if n_samples:
        # pathwise conditioning: the sample RHS columns solve against the
        # SAME operator as the mean, so the whole batch is one block solve.
        # Matheron's rule needs eps ~ N(0, sigma^2) with sigma^2 = the
        # ridge actually solved against — KRR with lam IS GP regression
        # with assumed noise variance lam, so the samples draw from that
        # model's posterior (not the data-generating noise=0.05)
        target, f_prior = gp_posterior_rhs(
            jax.random.fold_in(key, 2), x, ytr, laplace_kernel,
            n_train=n_train, n_samples=n_samples, noise=float(lam) ** 0.5)
    t0 = time.time()
    model = wlsh_krr_fit(jax.random.fold_in(key, 1), xtr, target, spec,
                         m=400, lam=lam, backend=args.backend,
                         precond=args.precond,
                         precond_rank=args.precond_rank,
                         solve_checkpoint_dir=args.solve_checkpoint_dir,
                         solve_checkpoint_every=args.solve_checkpoint_every)
    # batch_size streams the test set in fixed memory (O(batch * m) peak)
    pred_wlsh = wlsh_krr_predict(model, xte, batch_size=128)
    t_wlsh = time.time() - t0
    if n_samples:
        posterior_samples = f_prior[n_train:] + pred_wlsh[:, 1:]
        pred_wlsh = pred_wlsh[:, 0]
        spread = float(jnp.mean(jnp.std(posterior_samples, axis=1)))
    rmse_wlsh = float(jnp.sqrt(jnp.mean((pred_wlsh - fte) ** 2)))

    cg_iters = int(jnp.max(model.cg_col_iters))
    print(f"exact KRR : rmse={rmse_exact:.4f}  fit+predict={t_exact:.2f}s "
          f"(O(n^3) solve)")
    print(f"WLSH KRR  : rmse={rmse_wlsh:.4f}  fit+predict={t_wlsh:.2f}s "
          f"(backend={model.backend}, m=400 instances, O(n m) per CG "
          f"iteration, {cg_iters} iters, precond={model.precond})")
    if n_samples:
        print(f"GP posterior: {n_samples} pathwise samples in the same "
              f"solve; mean test-point std {spread:.4f}")
    assert rmse_wlsh < 2.0 * rmse_exact + 0.05, "WLSH should track exact KRR"

    # ---- serving round-trip: export the fitted model, load it back through
    # the online predictor, and check artifact == in-memory predictions ----
    if args.export:
        from repro.serve import export_artifact
        aid = export_artifact(args.export, model)
        print(f"serving    : exported artifact {aid!r} -> {args.export}")
    serve_dir = args.serve or args.export
    if args.serve is not None or args.export:
        import numpy as np
        from repro.serve import Predictor
        predictor = Predictor(backend=args.backend if args.backend != "auto"
                              else None, cache_entries=4096)
        aid = predictor.load(serve_dir)
        predictor.warmup(sizes=(1, 256))
        # a power-of-two query count keeps the predictor's padded shape equal
        # to the direct path's shape — shape-retiling ulps would otherwise
        # blur the bitwise round-trip signal
        xq = np.asarray(xte[:256], np.float32)
        served = predictor.predict(xq)
        bitwise = False
        compared = bool(args.export) and serve_dir == args.export
        if compared:
            # the artifact IS this run's fit: reference round-trip is
            # bitwise (same arrays, same program); across backends the
            # fused kernels regroup sums -> <=1e-6.  (a --serve-only dir
            # may hold any artifact, so there is nothing to compare then)
            direct = np.asarray(wlsh_krr_predict(model, xte[:256]))
            bitwise = np.array_equal(served, direct)
            assert bitwise or np.allclose(served, direct, atol=1e-6), \
                "served predictions diverged from the in-memory model"
        again = predictor.predict(xq)
        assert np.array_equal(again, served), "cache replay not bitwise"
        t0 = time.time()
        for row in np.asarray(xte[:64], np.float32):
            predictor.predict(row)
        per_query = (time.time() - t0) / 64
        verdict = ("round-trip bitwise" if bitwise else
                   "round-trip <=1e-6" if compared else
                   "served (no in-memory model to compare)")
        print(f"serving    : {verdict} over {len(served)} "
              f"test points; single-query warm+cache path "
              f"{per_query * 1e6:.0f}us/query "
              f"(cache hit rate "
              f"{predictor.cache_stats(artifact_id=aid)['hit_rate']:.2f})")
    print("OK")


if __name__ == "__main__":
    main()
