"""§4 data-structure claim: K~ beta in O(n) time / O(n) memory.

Times the WLSH matvec through the unified operator stack — exact sort mode,
the split CountSketch scatter→gather, and the fused one-pass slot-blocked
matvec, on each backend ('reference' jnp vs 'pallas' kernels) — across n,
against the O(n^2) dense matvec.  ``run`` returns JSON-able per-n rows with a
**stable schema** (every row carries every key; skipped measurements are
explicit ``None`` + a marker, never silently absent) so the perf trajectory
can accumulate in BENCH_matvec.json (see benchmarks/run.py) and
``benchmarks/check_regression.py`` can diff runs.

The solver section (``pcg_*`` keys) puts preconditioned CG on the same
regression rail: per n it solves an ill-conditioned synthetic KRR system
(long lengthscale, lam = 1e-3) unpreconditioned and with the rank-128
Nyström preconditioner, recording iteration counts and solve wall-clock.
``pcg_us`` includes the preconditioner build — the honest end-to-end cost.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

import jax

from repro import obs
from repro.core import (GammaPDF, get_bucket_fn, make_operator,
                        make_preconditioner, pcg_solve, sample_lsh_params,
                        table_diag)
from repro.core.precond import DEFAULT_NYSTROM_RANK
from repro.core.operator import default_table_size
from repro.core.wlsh import (build_exact_index, exact_kernel_matrix,
                             exact_matvec, table_matvec)

from .common import emit, refuse_on_tpu, time_fn

# dense comparison: build the true kernel matrix where the O(m n^2) featurized
# build fits in memory; above that use a random (n, n) proxy — the matvec cost
# only depends on the shape, and the timing is what the row records
DENSE_EXACT_MAX_N = 4096

# Reference fused-vs-split parity regime (measured, PR 5): at n >= this on
# CPU both paths are ~90% one XLA scatter-add (segment_sum for fused lowers
# to the same scatter loop as the split table scatter — 29ms vs 30ms of a
# ~33/38ms matvec at n=16384), so fused_speedup ~= 1.0 is the expected
# ceiling, NOT a pending win.  The fused path still saves the (m, B) table
# (4x the memory at B = 4n) and wins 1.5x+ at small n where table zeroing
# dominates.  Rows carry ``fused_parity_regime`` so downstream readers stop
# flagging ~1.0x as a regression.
FUSED_PARITY_MIN_N = 4096

# solver section: unpreconditioned CG on the ill-conditioned system needs
# O(1000) iterations — capped at this n so the benchmark stays minutes-scale
# (larger rows carry the explicit "large_n" skip marker instead)
PCG_MAX_N = 4096
PCG_LAM = 1e-3
PCG_LENGTHSCALE = 4.0
PCG_RANK = DEFAULT_NYSTROM_RANK
PCG_TOL = 1e-6
PCG_MAXITER = 2000

PCG_KEYS = ("cg_iters", "cg_us", "pcg_iters", "pcg_us", "pcg_iter_ratio")


def _pcg_section(key, x, m: int, table_size: int, row: dict) -> None:
    """Fill the row's solver keys (in place, always every key)."""
    d = x.shape[1]
    lsh = sample_lsh_params(jax.random.fold_in(key, 11), m, d,
                            GammaPDF(2.0, 1.0), lengthscale=PCG_LENGTHSCALE)
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="reference")
    idx = op.build_index(op.featurize(x))
    mv = lambda v: op.matvec(idx, v)
    y = jax.random.normal(jax.random.fold_in(key, 12), (x.shape[0],))
    diag = table_diag(idx.coeff)

    def plain():
        return pcg_solve(mv, y, PCG_LAM, tol=PCG_TOL, maxiter=PCG_MAXITER)

    def nystrom():
        pre = make_preconditioner("nystrom", matvec=mv, diag=diag,
                                  lam=PCG_LAM, rank=PCG_RANK)
        return pcg_solve(mv, y, PCG_LAM, precond=pre, tol=PCG_TOL,
                         maxiter=PCG_MAXITER)

    def timed_solve(solve):
        solve()                        # warmup: populate compile caches
        with obs.span("bench.pcg_solve"):
            res = jax.block_until_ready(solve())
        return int(res.iters), obs.span_samples_us("bench.pcg_solve")[-1]

    row["cg_iters"], row["cg_us"] = timed_solve(plain)
    row["pcg_iters"], row["pcg_us"] = timed_solve(nystrom)
    row["pcg_iter_ratio"] = row["cg_iters"] / max(row["pcg_iters"], 1)


def run(ns=(1024, 4096, 16384), d: int = 8, m: int = 16, seed: int = 0, *,
        timing_iters: int = 3, timing_stat: str = "median",
        with_dense: bool = True, with_pallas: bool = True,
        with_pcg: bool = True):
    """``timing_iters``/``timing_stat`` select the wall-clock protocol
    (median-of-3 for the committed trajectory; the regression gate uses
    min-of-many — see benchmarks/check_regression.py).  ``with_dense``/
    ``with_pallas``/``with_pcg`` drop the ungated sections for a fast gate
    rerun; dropped measurements stay in the row as explicit None + marker."""
    time_args = {"iters": timing_iters, "stat": timing_stat}
    f = get_bucket_fn("rect")
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for n in ns:
        key = jax.random.PRNGKey(seed)
        x = jax.random.uniform(key, (n, d)) * 2.0
        lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                                GammaPDF(2.0, 1.0))
        beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
        table_size = default_table_size(n, min_pow=10)

        op_ref = make_operator(lsh, f, table_size, backend="reference")
        feats = op_ref.featurize(x)
        tidx = op_ref.build_index(feats, blocked=False)   # split (no layout)
        fidx = op_ref.build_index(feats)                  # slot-blocked
        eidx = build_exact_index(feats)

        row = {"n": n, "m": m, "d": d, "table_size": table_size,
               "exact_us": time_fn(jax.jit(
                   lambda b: exact_matvec(eidx, b)), beta, **time_args) * 1e6,
               "reference_us": time_fn(jax.jit(
                   lambda b: table_matvec(tidx, b)), beta, **time_args) * 1e6,
               "fused_us": time_fn(jax.jit(
                   lambda b: op_ref.matvec(fidx, b)), beta,
                   **time_args) * 1e6}
        row["fused_speedup"] = row["reference_us"] / row["fused_us"]
        row["fused_parity_regime"] = (not on_tpu) and n >= FUSED_PARITY_MIN_N

        if with_dense:
            if n <= DENSE_EXACT_MAX_N:
                kmat = exact_kernel_matrix(feats)
                row["dense_proxy"] = False
            else:
                kmat = jax.random.normal(jax.random.fold_in(key, 3), (n, n))
                row["dense_proxy"] = True
            row["dense_us"] = time_fn(jax.jit(lambda b: kmat @ b), beta,
                                      **time_args) * 1e6
            del kmat
        else:
            row["dense_us"] = None
            row["dense_proxy"] = None

        if with_pallas and (on_tpu or n <= 1024):
            # off-TPU the Pallas kernels run in interpret mode (the kernel
            # body executes in Python) — correctness validation only,
            # meaningless as a wall-clock datapoint, so keep n tiny
            op_pal = make_operator(lsh, f, table_size, backend="pallas")
            fidx_pal = op_pal.build_index(feats)    # pallas layout group
            row["pallas_fused_us"] = time_fn(jax.jit(
                lambda b: op_pal.matvec(fidx_pal, b)), beta,
                **time_args) * 1e6
            # split contract (tables in HBM, psum-able) on the visit-list
            # schedule: the same index through loads then readout
            row["pallas_split_blocked_us"] = time_fn(jax.jit(
                lambda b: op_pal.readout(fidx_pal, op_pal.loads(fidx_pal, b))),
                beta, **time_args) * 1e6
            row["pallas_interpret"] = op_pal.interpret
            row["pallas_skipped"] = None
        else:
            row["pallas_fused_us"] = None
            row["pallas_split_blocked_us"] = None
            row["pallas_interpret"] = None
            row["pallas_skipped"] = "interpret" if with_pallas else "disabled"

        if not with_pcg:
            for k in PCG_KEYS:
                row[k] = None
            row["pcg_skipped"] = "disabled"
        elif n > PCG_MAX_N:
            for k in PCG_KEYS:
                row[k] = None
            row["pcg_skipped"] = "large_n"
        else:
            _pcg_section(key, x, m, table_size, row)
            row["pcg_skipped"] = None
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# distributed rows: the sharded psum / hash-join paths on a fake-CPU mesh
# ---------------------------------------------------------------------------

DIST_SHARDS = (2, 4)
DIST_NS = (1024, 4096)
DIST_CG_ITERS = 8

_DIST_SCRIPT = r"""
import json, sys, time
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import GammaPDF, get_bucket_fn, sample_lsh_params
from repro.core.operator import default_table_size
from repro.core.distributed import (KRRStepConfig, make_krr_step,
                                    make_krr_step_hashjoin)

shards = int(sys.argv[1])
ns = [int(v) for v in sys.argv[2].split(",")]
iters = int(sys.argv[3])
assert len(jax.devices()) == shards, jax.devices()
mesh = make_mesh((1, shards, 1), ("pod", "data", "model"))
f = get_bucket_fn("rect")
rows = []
for n in ns:
    d, m = 8, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    table_size = default_table_size(n, min_pow=10)
    cfg = KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=iters,
                        data_axes=("pod", "data"), model_axis="model",
                        backend="reference")

    yk = jax.random.normal(jax.random.fold_in(key, 3), (n, 8))

    def best(fn, tgt, reps=3):
        jax.block_until_ready(fn(x, tgt, lsh)[0])
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, tgt, lsh)[0])
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def iter_us(make, tgt=y, **kw):
        # isolate the per-CG-iteration (matvec + collectives) cost: the
        # cg_iters=0 step carries the same featurize/index/routing build
        full = best(jax.jit(make(mesh, cfg, f, **kw)), tgt)
        zero = best(jax.jit(make(mesh, cfg._replace(cg_iters=0), f, **kw)),
                    tgt)
        return max(full - zero, 0.0) / iters * 1e6

    # headline hashjoin_iter_us keeps cap_factor=4.0 + f32 wire — directly
    # comparable to the committed pre-fusion baseline
    hj = iter_us(make_krr_step_hashjoin, cap_factor=4.0,
                 payload_dtype=jnp.float32)
    hj_k8 = iter_us(make_krr_step_hashjoin, tgt=yk, cap_factor=4.0,
                    payload_dtype=jnp.float32)
    rows.append({"n": n, "shards": shards, "m": m, "table_size": table_size,
                 "cg_iters": iters, "psum_iter_us": iter_us(make_krr_step),
                 "hashjoin_iter_us": hj,
                 "hashjoin_bf16_iter_us": iter_us(make_krr_step_hashjoin,
                                                  cap_factor=4.0),
                 "hashjoin_k8_iter_us": hj_k8,
                 "hashjoin_k8_percol_ratio": hj_k8 / (8 * hj) if hj > 0
                 else None})
print("DISTROWS:" + json.dumps(rows))
"""


def distributed_rows(ns=DIST_NS, shard_counts=DIST_SHARDS,
                     cg_iters=DIST_CG_ITERS, timeout: float = 900.0):
    """Sharded-path timings, measured in subprocesses (the fake-CPU device
    count must be set before jax initializes, which this process already
    did).  Per (n, shards): the per-CG-iteration cost of the split psum
    matvec and the hash-join all_to_all matvec on a data mesh, isolated as
    (step(K iters) - step(0 iters)) / K so featurize/index/routing builds
    cancel.  Reference backend — interpret-mode Pallas timings are
    meaningless, and the collectives are the thing being recorded.  A
    failed shard count yields an explicit {"shards", "error"} marker row.
    Refused on a TPU host (``refuse_on_tpu``)."""
    refuse_on_tpu("bench_matvec.distributed_rows")
    root = pathlib.Path(__file__).resolve().parent.parent
    env = {"PYTHONPATH": str(root / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    out = []
    for s in shard_counts:
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _DIST_SCRIPT, str(s),
                 ",".join(map(str, ns)), str(cg_iters)],
                env={**env, "XLA_FLAGS":
                     f"--xla_force_host_platform_device_count={s}"},
                capture_output=True, text=True, cwd=str(root),
                timeout=timeout)
        except subprocess.TimeoutExpired:
            out.append({"shards": s, "error": "timeout"})
            continue
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("DISTROWS:")), None)
        if proc.returncode != 0 or line is None:
            out.append({"shards": s, "error": (proc.stderr or "no output")[-500:]})
            continue
        out.extend(json.loads(line[len("DISTROWS:"):]))
    return out


def _exponent(rows, key):
    """Empirical scaling exponent between the LAST two sizes (smaller ones
    are dominated by dispatch overhead); dense matvec would show ~2.0."""
    return float(np.log(rows[-1][key] / rows[-2][key]) /
                 np.log(rows[-1]["n"] / rows[-2]["n"]))


def calibration_us(iters: int = 10) -> float:
    """Fixed-shape dense matvec timed with the noise-robust min — a
    machine-speed yardstick stored next to the baseline rows so the
    regression gate can normalize away hardware differences between the
    committing machine and the checking one."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2048, 2048))
    v = jax.random.normal(jax.random.fold_in(key, 1), (2048,))
    return time_fn(jax.jit(lambda u: a @ u), v, iters=iters,
                   stat="min") * 1e6


def main(json_path: str | None = None, with_dist: bool = True) -> None:
    rows = run()
    print("n,exact_us,reference_us,fused_us,pallas_fused_us,"
          "pallas_split_blocked_us,dense_us")
    for r in rows:
        pal = ["skip" if r[k] is None else f"{r[k]:.1f}"
               for k in ("pallas_fused_us", "pallas_split_blocked_us")]
        print(f"{r['n']},{r['exact_us']:.1f},{r['reference_us']:.1f},"
              f"{r['fused_us']:.1f},{pal[0]},{pal[1]},{r['dense_us']:.1f}")
    for r in rows:
        if r["pcg_iters"] is not None:
            print(f"[pcg] n={r['n']}: cg {r['cg_iters']} iters "
                  f"({r['cg_us']:.0f}us) vs nystrom {r['pcg_iters']} iters "
                  f"({r['pcg_us']:.0f}us incl. build) — "
                  f"{r['pcg_iter_ratio']:.1f}x fewer iterations")
        else:
            print(f"[pcg] n={r['n']}: skipped ({r['pcg_skipped']})")
    dist = distributed_rows() if with_dist else []
    for r in dist:
        if "error" in r:
            print(f"[dist] shards={r['shards']}: FAILED {r['error'][:120]}")
        else:
            ratio = r.get("hashjoin_k8_percol_ratio")
            extra = (f" (bf16 {r['hashjoin_bf16_iter_us']:.0f}us, k=8 "
                     f"per-col {ratio:.2f}x)"
                     if ratio is not None else "")
            print(f"[dist] n={r['n']} shards={r['shards']}: psum "
                  f"{r['psum_iter_us']:.0f}us/iter, hash-join "
                  f"{r['hashjoin_iter_us']:.0f}us/iter{extra}")
    e_split = _exponent(rows, "reference_us")
    e_fused = _exponent(rows, "fused_us")
    if json_path:
        payload = {"bench": "matvec", "platform": jax.default_backend(),
                   "calib_us": calibration_us(),
                   "scaling_exponent": e_split,
                   "fused_scaling_exponent": e_fused, "rows": rows,
                   "distributed": dist}
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"[bench_matvec] wrote {json_path}")
    # report the fused win where it exists (small n); at large n on CPU
    # parity is the measured ceiling (FUSED_PARITY_MIN_N), not a pending win
    parity = rows[-1]["fused_parity_regime"]
    emit("bench_matvec", rows[-1]["fused_us"] * 1e-6,
         f"scaling_exponent split={e_split:.2f} fused={e_fused:.2f} "
         f"(1.0 = linear, dense = 2.0); "
         f"fused_speedup@n={rows[0]['n']}: {rows[0]['fused_speedup']:.2f}x"
         + (f"; parity expected at n>={FUSED_PARITY_MIN_N} (CPU scatter-add "
            f"bound)" if parity else
            f"; fused_speedup@n={rows[-1]['n']}: "
            f"{rows[-1]['fused_speedup']:.2f}x"))


if __name__ == "__main__":
    main()
