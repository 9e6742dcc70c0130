"""Kernel ridge regression solvers.

* ``pcg_solve`` — jittable preconditioned (block-)CG on (A + lam I) with an
  arbitrary matvec (the WLSH O(n) structure, an explicit matrix, or a
  distributed shard_map matvec — the solver only touches the operator
  through ``matvec``).  ``b`` may be (n,) or an (n, k) RHS block: all k
  systems share every matvec/preconditioner application, convergence is
  tracked per column, and converged columns are deflated (frozen) so their
  iterates stop changing while the stragglers finish.
* ``cg_solve`` — the historical single/unpreconditioned entry point, now a
  thin wrapper over ``pcg_solve`` (kept because every caller and test reads
  its scalar ``CGResult``).
* ``exact_krr_fit`` / ``exact_krr_predict`` — Cholesky baseline.
* ``wlsh_krr_fit`` / ``wlsh_krr_predict`` — the paper's §4.2 algorithm: solve
  (K̃ + lam I) beta = y with PCG, predict via bucket loads.

The WLSH path runs entirely through ``core.operator.WLSHOperator``, so the
same solver drives the jnp reference backend, the fused Pallas kernels
(``backend='pallas'``), or platform auto-selection (``backend='auto'``).
"""
from __future__ import annotations

import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..backend import platform_of
from ..errors import NonFiniteError, SolveDivergedError
from .bucket_fns import get_bucket_fn
from .kernels import WLSHKernelSpec
from .lsh import LSHParams, sample_lsh_params
from .operator import WLSHOperator, default_table_size, make_operator
from .precond import (DEFAULT_NYSTROM_RANK, Preconditioner, identity_precond,
                      make_preconditioner, table_diag)

Array = jnp.ndarray
MatVec = Callable[[Array], Array]

# The PCG loop's name in JAX's name stack.  ``cond`` and ``body`` are traced,
# so every op of the loop carries it in its HLO metadata, even when the
# while itself is dispatched eagerly.
PCG_SCOPE = "wlsh.pcg"


class CGResult(NamedTuple):
    x: Array
    iters: Array
    resnorm: Array


class PCGResult(NamedTuple):
    x: Array          # (n,) or (n, k) — solution block
    iters: Array      # scalar int32 — block iterations run (max over columns)
    col_iters: Array  # (k,) int32 — iteration at which each column converged
    resnorm: Array    # (k,) f32 — final per-column ||r||
    # (maxiter+1, k) per-iteration ||r_j||: row 0 is the initial residual,
    # row i the residual after block iteration i.  Rows past the final
    # iteration are NaN (static shape under jit); a deflated column's rows
    # freeze at its converged value, a deactivated column's go NaN.
    resnorm_history: Array | None = None


class SolveState(NamedTuple):
    """Serializable PCG state — everything ``pcg_solve`` needs to continue a
    solve from iteration ``it`` exactly where it left off.  Internals are
    always the 2-D block form ((n, k) even for a 1-D ``b``), so a persisted
    state round-trips through ``checkpoint/store.py`` (npz is bitwise for
    f32/int32/bool) and resumes on either calling convention."""

    x: Array          # (n, k) current iterates
    r: Array          # (n, k) residuals
    p: Array          # (n, k) search directions
    rs: Array         # (k,) ||r||² (NaN = column deactivated by a sentinel)
    rho: Array        # (k,) M⁻¹-inner products
    active: Array     # (k,) bool — still iterating
    it: Array         # scalar int32 — iterations completed
    col_iters: Array  # (k,) int32 — per-column convergence iteration


def solve_state_template(b: Array) -> SolveState:
    """Zero-filled ``SolveState`` shaped for RHS ``b`` — the restore template
    for ``checkpoint.restore_checkpoint``."""
    n = b.shape[0]
    k = 1 if b.ndim == 1 else b.shape[1]
    zk = np.zeros((k,), np.float32)
    znk = np.zeros((n, k), np.float32)
    return SolveState(x=znk, r=znk.copy(), p=znk.copy(), rs=zk,
                      rho=zk.copy(), active=np.zeros((k,), bool),
                      it=np.zeros((), np.int32),
                      col_iters=np.zeros((k,), np.int32))


def load_solve_state(directory: str, b: Array) -> SolveState | None:
    """Latest persisted ``SolveState`` under ``directory`` (None when the
    directory holds no complete checkpoint — a fresh solve)."""
    from ..checkpoint.store import latest_step, restore_checkpoint
    if latest_step(directory) is None:
        return None
    state, _, _ = restore_checkpoint(directory, solve_state_template(b))
    return jax.tree.map(jnp.asarray, state)


def pcg_solve(matvec: MatVec, b: Array, lam: float, *,
              precond: Preconditioner | None = None, tol: float = 1e-6,
              atol: float = 1e-12, maxiter: int = 200,
              x0: Array | None = None, state: SolveState | None = None,
              checkpoint_every: int = 0,
              on_checkpoint: Callable[[SolveState], None] | None = None,
              ) -> PCGResult:
    """Solve (A + lam I) X = B with preconditioned conjugate gradients.

    ``b`` is (n,) for one system or (n, k) for a RHS block; with a block the
    single matvec per iteration covers all k columns (the WLSH multi-RHS
    matvec amortizes the index walk — see WLSHOperator.matvec), and the CG
    recurrences run column-wise, so each column's trajectory is exactly the
    single-RHS trajectory it would have had alone.

    Per-column convergence when ``||r_j|| <= max(tol * ||b_j||, atol)`` —
    the absolute floor makes ``b_j = 0`` (and any exactly-solved system)
    terminate immediately instead of looping ``maxiter`` times on a zero
    threshold.  A converged column is deflated: its search direction is
    zeroed and its step sizes forced to 0, so its (x, r) freeze while the
    remaining columns iterate; the loop ends when every column is converged
    or at ``maxiter``.  All loop invariants (lam broadcast, thresholds,
    breakdown guard, preconditioner factors) are hoisted out of the
    iteration; each step costs one matvec, one preconditioner apply and
    three column-wise reductions.

    For a 1-D ``b`` the user matvec is only ever called with 1-D vectors
    (the block machinery runs on a width-1 column internally), so existing
    single-RHS matvec closures keep working unchanged.

    A column whose step goes non-finite (poisoned matvec, preconditioner
    breakdown) is deactivated BEFORE the bad update lands — its (x, r)
    freeze at the last finite iterate and its resnorm reports NaN, so the
    caller sees a sentinel instead of silent garbage while the healthy
    columns converge untouched.

    ``checkpoint_every > 0`` runs the loop in chunks of that many iterations
    and calls ``on_checkpoint(SolveState)`` after each chunk (eager mode
    only: the host loop syncs the iteration counter).  Pass a persisted
    ``state`` to resume — the trajectory continues bitwise where the saved
    chunk ended, so a preempted solve finishes within float tolerance of an
    uninterrupted one.  ``checkpoint_every = 0`` keeps the historical single
    while_loop (fully jittable).
    """
    vec = b.ndim == 1
    inner_mv = (lambda v: matvec(v[:, 0])[:, None]) if vec else matvec
    b2 = b[:, None] if vec else b
    lam = jnp.asarray(lam, b2.dtype)
    eps = jnp.asarray(1e-30, b2.dtype)           # breakdown guard, hoisted
    maxiter = int(maxiter)
    maxiter_a = jnp.asarray(maxiter, jnp.int32)
    psolve = (identity_precond() if precond is None else precond).apply

    def amv(v):
        return inner_mv(v) + lam * v

    bnorm = jnp.sqrt(jnp.sum(b2 * b2, axis=0))
    thresh = jnp.maximum(tol * bnorm, jnp.asarray(atol, b2.dtype)) ** 2

    # per-iteration residual telemetry: NaN-filled (maxiter+1, k), rows
    # written as the solve progresses — carried OUTSIDE SolveState so
    # persisted checkpoints keep their npz schema (a resumed solve records
    # from its resume row; earlier rows stay NaN)
    hist = jnp.full((maxiter + 1, b2.shape[1]), jnp.nan, b2.dtype)
    if state is None:
        if x0 is None:
            x = jnp.zeros_like(b2)
        else:
            x = x0[:, None] if vec else x0
        r = b2 - amv(x)
        z = psolve(r)
        rs = jnp.sum(r * r, axis=0)              # (k,) true residual norms²
        rho = jnp.sum(r * z, axis=0)             # (k,) M⁻¹-inner products
        active = rs > thresh
        p = jnp.where(active[None, :], z, 0.0)
        col_iters = jnp.where(active, maxiter_a, 0).astype(jnp.int32)
        state = SolveState(x=x, r=r, p=p, rs=rs, rho=rho, active=active,
                           it=jnp.asarray(0, jnp.int32),
                           col_iters=col_iters)
    hist = hist.at[state.it].set(jnp.sqrt(state.rs))
    chunk = int(checkpoint_every) if checkpoint_every > 0 else maxiter

    @jax.named_scope(PCG_SCOPE)
    def cond(carry):
        steps, st, _ = carry
        return jnp.any(st.active) & (st.it < maxiter_a) & (steps < chunk)

    @jax.named_scope(PCG_SCOPE)
    def body(carry):
        steps, st, hist = carry
        x, r, p, rs, rho, active, it, col_iters = st
        ap = amv(p)
        denom = jnp.sum(p * ap, axis=0)
        alpha = rho / jnp.maximum(denom, eps)
        # non-finite sentinel: a NaN/Inf step (poisoned ap, broken psolve)
        # never lands on (x, r) — the column deactivates with rs = NaN
        ok = active & jnp.isfinite(alpha)
        alpha = jnp.where(ok, alpha, 0.0)
        x = x + jnp.where(ok[None, :], alpha[None, :] * p, 0.0)
        r = r - jnp.where(ok[None, :], alpha[None, :] * ap, 0.0)
        rs = jnp.sum(r * r, axis=0)
        rs = jnp.where(active & ~ok, jnp.nan, rs)
        hist = hist.at[it + 1].set(jnp.sqrt(rs))
        # a column whose residual goes non-finite (preconditioner breakdown
        # at extreme conditioning) is deactivated instead of burning the
        # remaining iterations on NaNs; its resnorm reports the failure
        still = (rs > thresh) & jnp.isfinite(rs)
        col_iters = jnp.where(active & ~still, it + 1, col_iters)
        active = active & still
        z = psolve(r)
        rho_new = jnp.sum(r * z, axis=0)
        beta = jnp.where(active, rho_new / jnp.maximum(rho, eps), 0.0)
        # deflation: converged columns get p = 0, so alpha·p and alpha·ap
        # vanish and their (x, r) are frozen from here on
        p = jnp.where(active[None, :], z + beta[None, :] * p, 0.0)
        return steps + 1, SolveState(x, r, p, rs, rho_new, active, it + 1,
                                     col_iters), hist

    def run_chunk(st: SolveState, hist: Array):
        _, st, hist = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), st, hist))
        return st, hist

    if chunk >= maxiter:                         # historical one-shot path
        state, hist = run_chunk(state, hist)
        if on_checkpoint is not None:
            on_checkpoint(state)
    else:
        while True:                              # eager chunked/checkpointed
            state, hist = run_chunk(state, hist)
            if on_checkpoint is not None:
                on_checkpoint(state)             # may raise (preemption)
            if int(state.it) >= maxiter or not bool(jnp.any(state.active)):
                break
    # columns still active at maxiter report maxiter (their init value)
    resnorm = jnp.sqrt(state.rs)
    return PCGResult(x=state.x[:, 0] if vec else state.x, iters=state.it,
                     col_iters=state.col_iters, resnorm=resnorm,
                     resnorm_history=hist)


def cg_solve(matvec: MatVec, b: Array, lam: float, *, tol: float = 1e-6,
             atol: float = 1e-12, maxiter: int = 200,
             x0: Array | None = None) -> CGResult:
    """Unpreconditioned single-RHS CG — wrapper over ``pcg_solve`` returning
    the scalar-shaped ``CGResult`` the historical callers expect."""
    res = pcg_solve(matvec, b, lam, tol=tol, atol=atol, maxiter=maxiter,
                    x0=x0)
    squeeze = b.ndim == 1
    return CGResult(x=res.x,
                    iters=res.iters if not squeeze else res.col_iters[0],
                    resnorm=res.resnorm[0] if squeeze else res.resnorm)


# ---------------------------------------------------------------------------
# exact KRR (dense baseline)
# ---------------------------------------------------------------------------

def exact_krr_fit(kernel_fn, x: Array, y: Array, lam: float) -> Array:
    k = kernel_fn(x, x)
    n = x.shape[0]
    a = k + lam * jnp.eye(n, dtype=k.dtype)
    return jnp.linalg.solve(a, y)


def exact_krr_predict(kernel_fn, x_train: Array, beta: Array, x_test: Array) -> Array:
    return kernel_fn(x_test, x_train) @ beta


# ---------------------------------------------------------------------------
# WLSH approximate KRR (paper §4.2)
# ---------------------------------------------------------------------------

class WLSHKRRModel(NamedTuple):
    lsh: LSHParams
    bucket_name: str
    beta: Array           # (n,) or (n, k) PCG solution of (K̃ + lam I) b = y
    tables: Array         # (m, B[, k]) bucket loads of beta — all prediction
    table_size: int       # needs (k columns for a multi-RHS fit)
    cg_iters: Array
    cg_resnorm: Array
    backend: str = "reference"   # concrete backend the model was fit with
    precond: str = "none"        # preconditioner the solve used
    cg_col_iters: Array | None = None  # (k,) per-column iteration counts
    solve_fallback: str = ""     # nonempty when a one-shot fallback ran
                                 # (e.g. "precond:jacobi->identity")
    telemetry: dict | None = None
    # Solver telemetry captured at fit time (eager fits only; None under
    # jit and for models restored from pre-telemetry artifacts):
    #   resnorm_history — (iters+1, k) np.float32 per-iteration per-column
    #                     ||r|| (row 0 = initial residual)
    #   col_iters, iters, precond, fallback — solve summary
    # Retrievable WITHOUT refitting: it rides on the model tuple.


def model_operator(model: WLSHKRRModel, *,
                   backend: str | None = None) -> WLSHOperator:
    """Rebuild the operator a fitted model was trained with (optionally
    overriding the backend — all backends read the same tables), for the
    platform its tables live on."""
    return make_operator(model.lsh, get_bucket_fn(model.bucket_name),
                         model.table_size,
                         backend=backend if backend is not None
                         else model.backend,
                         platform=platform_of(model.tables))


def wlsh_krr_fit(key: jax.Array, x: Array, y: Array, spec: WLSHKernelSpec, *,
                 m: int, lam: float, mode: str = "table", table_size: int = 0,
                 tol: float = 1e-5, atol: float = 1e-12, maxiter: int = 400,
                 backend: str | None = "auto",
                 precond: str = "none",
                 precond_rank: int = DEFAULT_NYSTROM_RANK,
                 nonfinite_targets: str = "raise",
                 solve_checkpoint_dir: str | None = None,
                 solve_checkpoint_every: int = 0,
                 on_solve_checkpoint=None) -> WLSHKRRModel:
    """In table mode the index carries the slot-blocked layout, so every
    CG matvec is the fused one-pass path (``WLSHOperator.matvec``) and the
    final tables come from the split scatter ``op.loads``; on the reference
    backend the fused path is bitwise the split readout ∘ loads.
    ``tol``/``atol`` are the PCG relative / absolute residual thresholds
    (see ``pcg_solve``).

    ``y`` is (n,) for a plain fit or (n, k) for a batched multi-RHS fit
    (k targets — e.g. the GP posterior-sample block from core/gp.py — share
    the index build and every solver matvec; see ``pcg_solve``).

    ``precond`` selects the solver preconditioner ('none' | 'jacobi' |
    'nystrom', see core/precond.py); 'nystrom' builds its rank-
    ``precond_rank`` pivoted factorization with one extra multi-RHS matvec
    before the solve and typically cuts ill-conditioned (small-lam)
    iteration counts by well over 3x.

    Resilience (DESIGN.md §9): ``nonfinite_targets`` controls what a NaN/Inf
    in ``x``/``y`` does — 'raise' (default) rejects the fit with a structured
    ``NonFiniteError`` before any compute; 'deactivate' lets the solver's
    sentinel logic freeze the poisoned columns (their resnorm reports NaN,
    beta stays finite).  A non-finite PCG residual under a non-identity
    preconditioner triggers ONE restart with the identity preconditioner
    (recorded in ``model.solve_fallback``); if beta is still non-finite the
    fit raises ``SolveDivergedError`` rather than return garbage.

    ``solve_checkpoint_dir`` persists the solver's ``SolveState`` every
    ``solve_checkpoint_every`` iterations (default maxiter//10) through
    ``checkpoint/store.py`` and RESUMES from the newest complete state in
    that directory — a preempted fit restarted with the same arguments
    continues where it left off.  ``on_solve_checkpoint`` (called after each
    persisted state) is the test hook that simulates the preemption."""
    if nonfinite_targets not in ("raise", "deactivate"):
        raise ValueError(f"nonfinite_targets must be 'raise' or "
                         f"'deactivate', got {nonfinite_targets!r}")
    if nonfinite_targets == "raise":
        for name, arr in (("x", x), ("y", y)):
            if isinstance(arr, jax.core.Tracer):
                continue                   # traced fit: host check impossible
            bad = int(jnp.sum(~jnp.isfinite(arr)))
            if bad:
                raise NonFiniteError(
                    f"{bad} non-finite value(s) in training {name}; clean "
                    f"the data or pass nonfinite_targets='deactivate'",
                    where=name, count=bad)
    n, d = x.shape
    if table_size <= 0:
        # heuristic: ~4x points per instance keeps same-slot collisions rare
        table_size = default_table_size(n)
    lsh = sample_lsh_params(key, m, d, spec.pdf, spec.lengthscale)
    op = make_operator(lsh, get_bucket_fn(spec.bucket.name), table_size,
                       backend=backend, platform=platform_of(x))
    # the fit.* spans time host work and dispatch (nothing here waits for
    # the device): they name the host's share of a trace's idle gaps
    with obs.span("fit.featurize", {"n": n, "m": m}):
        feats = op.featurize(x)

    # Prediction tables are always CountSketch (exact-mode key lookup for
    # out-of-sample points would need a hash join; the signed table is unbiased
    # and O(1) per query — see DESIGN.md §3).  In table mode the same index
    # drives CG, so it is built exactly once (the CG closure closes over the
    # slot-blocked layout — the sort runs once, not per iteration).
    with obs.span("fit.build_index", {"mode": mode}):
        tidx = op.build_index(feats, mode="table",
                              blocked=mode == "table")
        if mode == "exact":
            eidx = op.build_index(feats, mode="exact")
    if mode == "exact":
        mv = lambda v: op.matvec(eidx, v)
        diag = jnp.mean(eidx.weight * eidx.weight, axis=0)
    elif mode == "table":
        mv = lambda v: op.matvec(tidx, v)
        diag = table_diag(tidx.coeff)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    pre = make_preconditioner(precond, matvec=mv, diag=diag, lam=lam,
                              rank=precond_rank)

    state = None
    every = int(solve_checkpoint_every)
    on_ck = on_solve_checkpoint if every > 0 else None
    if solve_checkpoint_dir:
        from ..checkpoint.store import CheckpointManager
        if every <= 0:
            every = max(1, maxiter // 10)
        mgr = CheckpointManager(solve_checkpoint_dir, keep=2)
        state = load_solve_state(solve_checkpoint_dir, y)

        def on_ck(st):
            # persist FIRST, then fire the test hook: a preemption injected
            # by the hook leaves this chunk's state already on disk
            mgr.save(int(st.it), st, blocking=True)
            if on_solve_checkpoint is not None:
                on_solve_checkpoint(st)

    with obs.span("fit.pcg_solve", {"precond": precond, "maxiter": maxiter}):
        res = pcg_solve(mv, y, lam, precond=pre, tol=tol, atol=atol,
                        maxiter=maxiter, state=state, checkpoint_every=every,
                        on_checkpoint=on_ck)
    fallback = ""
    eager = not isinstance(res.resnorm, jax.core.Tracer)
    if eager and precond not in ("none", None) \
            and not bool(jnp.all(jnp.isfinite(res.resnorm))):
        # one-shot fallback: a diverged preconditioned solve restarts once
        # with the identity preconditioner before giving up
        warnings.warn(f"PCG with precond={precond!r} went non-finite; "
                      f"restarting once with the identity preconditioner",
                      RuntimeWarning, stacklevel=2)
        obs.counter("fit_precond_fallback_total",
                    "preconditioned solves restarted with identity",
                    labels=("precond",)).labels(precond).inc()
        fallback = f"precond:{precond}->identity"
        res = pcg_solve(mv, y, lam, precond=None, tol=tol, atol=atol,
                        maxiter=maxiter)
    if eager and not bool(jnp.all(jnp.isfinite(res.x))):
        raise SolveDivergedError(
            "PCG iterates are non-finite after all fallbacks",
            resnorm=np.asarray(res.resnorm),
            fallbacks=(fallback,) if fallback else ())
    tables = op.loads(tidx, res.x)
    squeeze = y.ndim == 1
    telemetry = None
    if eager:
        # host-side solve summary + per-iteration residuals, attached to
        # the model so it is retrievable without refitting
        iters = int(res.iters)
        dead = int(jnp.sum(~jnp.isfinite(res.resnorm)))
        obs.counter("fit_solves_total", "wlsh_krr_fit solves completed").inc()
        obs.gauge("fit_pcg_iters",
                  "block iterations of the most recent fit solve").set(iters)
        obs.histogram("fit_pcg_iters_hist",
                      "distribution of PCG iteration counts per solve",
                      buckets=obs.COUNT_BUCKETS).observe(iters)
        if dead:
            obs.counter("fit_col_deactivated_total",
                        "RHS columns deactivated by non-finite sentinels"
                        ).inc(dead)
        telemetry = {
            "resnorm_history": np.asarray(
                res.resnorm_history[: iters + 1], np.float32),
            "col_iters": np.asarray(res.col_iters, np.int32),
            "iters": iters,
            "precond": precond,
            "fallback": fallback,
        }
    return WLSHKRRModel(lsh=lsh, bucket_name=spec.bucket.name, beta=res.x,
                        tables=tables, table_size=table_size,
                        cg_iters=res.col_iters[0] if squeeze else res.iters,
                        cg_resnorm=res.resnorm[0] if squeeze
                        else res.resnorm,
                        backend=op.backend, precond=precond,
                        cg_col_iters=res.col_iters,
                        solve_fallback=fallback,
                        telemetry=telemetry)


def wlsh_krr_predict(model: WLSHKRRModel, x_test: Array, *,
                     batch_size: int | None = None,
                     backend: str | None = None) -> Array:
    """Predict at x_test from the model's bucket-load tables.  ``batch_size``
    streams the test set in fixed-memory blocks (multi-million-point
    inference never materializes an (m, n_test) featurization).  A model fit
    on an (n, k) RHS block predicts all k columns at once: (n_test, k)."""
    op = model_operator(model, backend=backend)
    return op.predict_batched(model.tables, x_test, batch_size=batch_size)
