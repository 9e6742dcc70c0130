"""Fit cells: whole fits to tolerance, back to back (closed loop).

Set-up makes the configuration's data, picks the lengthscale by the
median-L1 heuristic and runs one fit, which compiles (or loads from the
compile cache) every program a fit uses.  The window then runs fits one
after another until ``seconds`` have passed on the same data, each ended
by ``block_until_ready`` on the model's tables.  Their LSH keys come from a
fixed pool of the mix's ``key_pool`` keys, taken round after round in an
order drawn from the seed.  The data and the keys decide how many PCG
iterations a fit takes, so both are the same for every seed: every seed
gets the same work, and only its order.  ``fit_s`` is the summed wall
time of those fits over their number.  Every fit of the window is then
checked against the plain reference: its residual on the reference system
and its tables against the loads of its beta (``reference.fit_gaps``).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import data, harness, reference

FIT_STREAM = 2000
POOL_STREAM = 3000          # the mix's fixed work: seed 0's streams
DATA_STREAM = 3001


class FitOut(NamedTuple):
    beta: object            # (n,) device array
    tables: object          # (m, B) device array
    iters: int
    converged: bool


class ProgramFit:
    """The system under test: ``repro.core.krr.wlsh_krr_fit`` at the
    configuration's settings."""

    def __init__(self, cfg: dict, x, y, lengthscale: float):
        from repro import core
        self.cfg, self.x, self.y = cfg, x, y
        self.stop = cfg["tol"] * float(np.linalg.norm(np.asarray(y)))
        self.spec = core.WLSHKernelSpec(
            bucket=core.get_bucket_fn(cfg["bucket"]),
            pdf=core.GammaPDF(*cfg["gamma_pdf"]), lengthscale=lengthscale)

    def fit(self, key) -> FitOut:
        import jax
        from repro.core import krr
        c = self.cfg
        model = krr.wlsh_krr_fit(
            key, self.x, self.y, self.spec, m=c["m"],
            lam=c["lam_per_point"] * c["n_train"],
            table_size=c["table_size"], tol=c["tol"], maxiter=c["maxiter"],
            backend=c["backend"])
        jax.block_until_ready(model.tables)
        return FitOut(model.beta, model.tables, int(model.cg_iters),
                      float(model.cg_resnorm) <= self.stop)


def key_pool(size: int) -> list:
    """The mix's LSH keys, the same for every seed; one more, the last,
    for the warm-up fit."""
    return [harness.seed_key(0, POOL_STREAM, i) for i in range(size + 1)]


def key_index(seed: int, size: int, fit: int) -> int:
    """The pool index of the window's ``fit``-th key: the pool is taken
    round after round, each round in a permutation drawn from the seed."""
    order = harness.seed_rng(seed, FIT_STREAM, fit // size).permutation(size)
    return int(order[fit % size])


class State(NamedTuple):
    cfg: dict
    seed: int
    x: object
    y: object
    lengthscale: float
    system: object
    pool: list              # the window's LSH keys


def setup(cell, seed: int, seconds: float, system=None) -> State:
    """``system`` builds the fitter from (cfg, x, y, lengthscale); the
    program's ``ProgramFit`` unless a control or a test stands in."""
    import jax
    cfg = cell.config
    x, y, _, _ = jax.block_until_ready(
        data.make_set(cfg, harness.seed_key(0, DATA_STREAM)))
    ls = data.lengthscale(x)
    system = (system or ProgramFit)(cfg, x, y, ls)
    pool = key_pool(int(cell.traffic["key_pool"]))
    system.fit(pool.pop())
    return State(cfg, seed, x, y, ls, system, pool)


class Window(NamedTuple):
    seconds: list            # wall time of each fit
    outs: list               # FitOut of each fit that returned
    keys: list
    errors: list
    attempted: int
    failed: int
    e2e: dict


def measure(st: State, seconds: float, tracing: bool = False) -> Window:
    note = harness.annotation if tracing else harness.no_annotation
    times, outs, keys, errors = [], [], [], []
    t_start = harness.now()
    with note("chipbench.window"):
        while harness.now() - t_start < seconds:
            key = st.pool[key_index(st.seed, len(st.pool), len(times))]
            t0 = harness.now()
            try:
                with note("chipbench.fit"):
                    out = st.system.fit(key)
            except Exception as e:                # noqa: BLE001 — counted
                errors.append(repr(e))
                out = None
            times.append(harness.now() - t0)
            if out is not None:
                outs.append(out)
                keys.append(key)
    failed = len(errors) + sum(not o.converged for o in outs)
    return Window(times, outs, keys, errors, len(times), failed,
                  {"fit_s": sum(times) / len(times)})


def release(st: State, win: Window) -> Window:
    """Copy each fit's output to the host and drop the program's state."""
    outs = [o._replace(beta=np.asarray(o.beta), tables=np.asarray(o.tables))
            for o in win.outs]
    return win._replace(outs=outs)


def check(st: State, win: Window) -> dict:
    """Worst residual and tables gap over the window's fits, each with its
    limit from the configuration, and the count of fits that raised or
    stopped at ``maxiter`` above the tolerance."""
    c = st.cfg
    lam = c["lam_per_point"] * c["n_train"]
    worst = {"residual": 0.0, "tables": 0.0}
    for key, out in zip(win.keys, win.outs):
        lsh = reference.sample_lsh(key, c["m"], c["d"], *c["gamma_pdf"],
                                   st.lengthscale)
        hashes = reference.hash_points(lsh, st.x, c["table_size"])
        gaps = reference.fit_gaps(hashes, out.beta, st.y, lam, out.tables,
                                  c["table_size"])
        for k in worst:
            worst[k] = max(worst[k], gaps[k])
    limits = c["limits"]
    return {"fit_residual": (worst["residual"], limits["fit_residual"]),
            "fit_tables_gap": (worst["tables"], limits["fit_tables_gap"]),
            "fits_failed": (float(win.failed), 0.0)}


def layer_info(st: State, win: Window) -> dict:
    c = st.cfg
    return {"fits": len(win.seconds), "pcg_iters": [o.iters for o in win.outs],
            "fit_seconds": win.seconds,
            "n": c["n_train"], "m": c["m"], "d": c["d"], "k": 1}
