#!/usr/bin/env python3
"""The controls: the plain reference put in the program's place, computed
one precision below what the configuration states (bfloat16 for float32).
A check that cannot tell the control from the program holds nothing.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --program-seeds 1,2,...,12 --control-seeds 21,22,23

runs, in one process on the chip, the cell's set-up, window and check for
each program seed and then with the control in the program's place for
each control seed, and prints one JSON line per run with its compared
numbers: the readings from which the limits are set.
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def _reference_fit(lsh, x, y, lam, *, table_size, tol, maxiter, dtype):
    """Plain CG on (K~ + lam I) beta = y with every array and sum in
    ``dtype``; returns (beta, tables, iterations), beta and tables f32."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    slot, sign = reference.hash_device(lsh, x, table_size)
    sign = sign.astype(dtype)
    m = slot.shape[0]
    rows = jnp.arange(m)[:, None]

    def loads(v):
        return jax.vmap(lambda s, g: jax.ops.segment_sum(
            g * v, s, num_segments=table_size))(slot, sign)

    def mv(v):
        return jnp.mean(sign * loads(v)[rows, slot], axis=0) + lam_d * v

    b = y.astype(dtype)
    lam_d = jnp.asarray(lam, dtype)
    stop = (tol * jnp.linalg.norm(y)) ** 2

    def cond(c):
        _, _, _, rs, it = c
        return (rs.astype(jnp.float32) > stop) & (it < maxiter)

    def body(c):
        xk, r, p, rs, it = c
        ap = mv(p)
        alpha = rs / jnp.sum(p * ap)
        xk = xk + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r)
        return xk, r, r + (rs_new / rs) * p, rs_new, it + 1

    init = (jnp.zeros_like(b), b, b, jnp.sum(b * b), jnp.asarray(0))
    beta, _, _, _, it = jax.lax.while_loop(cond, body, init)
    return (beta.astype(jnp.float32), loads(beta).astype(jnp.float32), it)


class ReferenceFit:
    """The reference fit in ``dtype``, with the program fit's interface."""

    def __init__(self, cfg, x, y, lengthscale, dtype):
        import jax
        self.cfg, self.x, self.y, self.ls = cfg, x, y, lengthscale
        c = cfg
        self._fit = jax.jit(functools.partial(
            _reference_fit, table_size=c["table_size"], tol=c["tol"],
            maxiter=c["maxiter"], dtype=dtype))
        self.lam = c["lam_per_point"] * c["n_train"]

    def fit(self, key):
        import jax
        from chipbench import fit_loop, reference
        c = self.cfg
        lsh = reference.sample_lsh(key, c["m"], c["d"], *c["gamma_pdf"],
                                   self.ls)
        beta, tables, it = jax.block_until_ready(
            self._fit(lsh, self.x, self.y, self.lam))
        return fit_loop.FitOut(beta, tables, int(it),
                              int(it) < c["maxiter"])


class ReferenceServe:
    """The reference readout in ``dtype``, padded to power-of-two batches
    like the program, with the program server's interface."""

    def __init__(self, cfg, lsh, tables, dtype):
        import jax
        import jax.numpy as jnp
        from chipbench import reference
        self.lsh, self.tables = lsh, tables.astype(dtype)
        self.max_batch = cfg["serve"]["predictor_max_batch"]
        m = tables.shape[0]

        def answer(lsh, t, x):
            slot, sign = reference.hash_device(lsh, x, t.shape[1])
            vals = t[jnp.arange(m)[:, None], slot]
            return jnp.mean(sign.astype(dtype) * vals, axis=0).astype(
                jnp.float32)

        self._answer = jax.jit(answer)

    def warm(self, sizes):
        for b in sizes:
            self.predict(np.zeros((b, self.lsh[0].shape[1]), np.float32))

    def predict(self, x):
        x = np.asarray(x, np.float32)
        out = []
        for i in range(0, len(x), self.max_batch):
            chunk = x[i:i + self.max_batch]
            bucket = 1 << (len(chunk) - 1).bit_length()
            pad = np.zeros((bucket, x.shape[1]), np.float32)
            pad[:len(chunk)] = chunk
            out.append(np.asarray(self._answer(self.lsh, self.tables,
                                               pad))[:len(chunk)])
        return np.concatenate(out)


def control_system(cell, dtype):
    """A factory the drivers call in place of the program's system."""
    kind = cell.traffic["kind"]
    if kind == "fit_loop":
        return lambda cfg, x, y, ls: ReferenceFit(cfg, x, y, ls, dtype)
    return lambda cfg, lsh, tables: ReferenceServe(cfg, lsh, tables, dtype)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    harness.prepare_process()
    cell = harness.Cell(args.workload)
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.cache_every_program()
    import jax.numpy as jnp
    from chipbench.run import run_cell
    runs = [(int(s), None) for s in args.program_seeds.split(",") if s] + \
        [(int(s), control_system(cell, jnp.bfloat16))
         for s in args.control_seeds.split(",") if s]
    for seed, system in runs:
        t0 = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, devices,
                       system=system, t_start=t0)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "system": "program" if system is None
                          else "control_bf16",
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
