"""Kernel probe: the WLSH kernels, and the variants they were chosen over,
timed one by one on one device at the Forest Cover fit shape.

    PYTHONPATH=src python -m benchmarks.kernel_probe [--json PATH]
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.kernel_probe \\
        --scale 0.004 --m 4          # CPU: kernels interpreted, tiny shape

Each line gives a kernel's first call (compiles included) and its steady
time (median of 3 after the first call), in seconds, host clock around
``block_until_ready``.  Probed, at d=54, n = 500,000·scale, m instances,
B = default_table_size(n), rect bucket:

- featurize: the Pallas kernel on all n points; the reference on 16,384
  points, and the number of hashes the two disagree on;
- the slot-blocked layout build (``build_index(blocked=True)``);
- the matvec: the fused kernel with k=1 and k=4 right-hand sides, the same
  kernel with its one-hot products as MXU matmuls at HIGHEST precision
  (the formulation the select-and-reduce replaced), the reference sorted
  segment-sum, and the blocked split scatter + gather;
- the readout of 1024 query points from (m, B) tables: XLA's row gather
  ``tables[s, slot]``, and the serving readout (``bin_readout_op`` on the
  layout-less query index: that row gather, the coefficients and the
  instance mean).

Variants are checked against the kernel they stand in for and the largest
difference is printed.  Times from a CPU run say nothing about a device.
"""
from __future__ import annotations

import argparse
import json
import time
import types

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.core import GammaPDF, get_bucket_fn, make_operator, \
    sample_lsh_params
from repro.core.operator import default_table_size
from repro.data import make_regression_dataset
from repro.kernels.binning import kernel as bk
from repro.kernels.binning.ops import bin_readout_op

from .common import time_fn
from .table2_krr import _median_dists

QUERIES = 1024
FEATURIZE_REF_N = 16_384
_HIGHEST = jax.lax.Precision.HIGHEST


def _scatter_mxu(hit, contrib):
    """``kernel._scatter`` as one MXU product: (k, bn) x (bt, bn)^T."""
    rows = jax.lax.dot_general(contrib, hit.astype(jnp.float32),
                               (((1,), (1,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    return bk._rows_to_cols(rows)


def _gather_mxu(hit, cols):
    """``kernel._gather`` as one MXU product: (k, bt) x (bt, bn)."""
    return jax.lax.dot_general(bk._cols_to_rows(cols),
                               hit.astype(jnp.float32),
                               (((1,), (0,)), ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _with_globals(fn, **names):
    """A copy of ``fn`` that sees ``names`` in place of its module's
    globals: the kernel's own code, with only the named helpers swapped."""
    fn = getattr(fn, "__wrapped__", fn)
    g = {**fn.__globals__, **names}
    out = types.FunctionType(fn.__code__, g, fn.__name__, fn.__defaults__,
                             fn.__closure__)
    out.__kwdefaults__ = fn.__kwdefaults__
    return out


_MXU = dict(_scatter=_scatter_mxu, _gather=_gather_mxu)
fused_mxu = _with_globals(bk.bin_fused_matvec_pallas,
                          _fused_body=_with_globals(bk._fused_body, **_MXU))


def _on_index(fn, index):
    """jit ``fn(index, *args)`` with the index's int fields (table size,
    block sizes) held static: as a jit argument they would be traced."""
    leaves, treedef = jax.tree.flatten(index)
    is_int = [isinstance(v, int) for v in leaves]
    ints = [v for v, s in zip(leaves, is_int) if s]
    arrays = [v for v, s in zip(leaves, is_int) if not s]

    @jax.jit
    def run(arrs, *args):
        a, i = iter(arrs), iter(ints)
        full = [next(i) if s else next(a) for s in is_int]
        return fn(jax.tree.unflatten(treedef, full), *args)
    return lambda *args: run(arrays, *args)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="fraction of the Forest Cover training set")
    ap.add_argument("--m", type=int, default=64, help="LSH instances")
    ap.add_argument("--json", help="write the readings here as JSON")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    results: dict = {}

    def probe(name, fn, *a):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        first = time.perf_counter() - t0
        steady = time_fn(fn, *a, warmup=0, iters=3, span=f"probe.{name}")
        results[name] = {"first_s": first, "steady_s": steady}
        print(f"{name}: first {first:.4f}s steady {steady:.4f}s", flush=True)
        return out

    def differ(name, a, b):
        diff = float(jnp.max(jnp.abs(a - b)))
        results[name] = diff
        print(f"{name}: max |diff| {diff:.3e}", flush=True)

    xtr, _, xte, _ = make_regression_dataset("forest", 0, scale=args.scale)
    xtr, xte = jax.device_put((xtr, xte), dev)
    n, d = xtr.shape
    table_size = default_table_size(n)
    ell = float(_median_dists(xtr, jax.random.PRNGKey(3))[0]) / 2.0
    lsh = sample_lsh_params(jax.random.PRNGKey(1), args.m, d,
                            GammaPDF(2.0, 1.0), ell)
    f = get_bucket_fn("rect")
    pal, ref = (make_operator(lsh, f, table_size, backend=b,
                              platform=dev.platform)
                for b in ("pallas", "reference"))
    print(f"n={n} d={d} m={args.m} B={table_size}", flush=True)

    feats = probe("featurize pallas", jax.jit(pal.featurize), xtr)
    xs = xtr[:FEATURIZE_REF_N]
    fr = probe("featurize reference 16k", jax.jit(ref.featurize), xs)
    flips = int(jnp.sum((feats.key1[:, :xs.shape[0]] != fr.key1)
                        | (feats.key2[:, :xs.shape[0]] != fr.key2)))
    results["featurize hash mismatches"] = flips
    print(f"featurize hash mismatches: {flips} of {fr.key1.size}", flush=True)

    index = probe("build_index blocked",
                  lambda fe: pal.build_index(fe, parts="both"), feats)
    lay = index.blocked
    print(f"visits: real max {int(jnp.max(lay.n_visits))}, static "
          f"{lay.v_block.shape[1]}", flush=True)
    beta = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    y = probe("fused matvec k=1", _on_index(pal.matvec, index), beta)
    interpret = pal.interpret

    def mxu_matvec(idx, b):
        blay = idx.blocked
        tail = -n % blay.block_n
        beta_sorted = jnp.pad(b[blay.order], ((0, 0), (0, tail)))
        out = fused_mxu(blay.v_block, blay.v_tile_phase, blay.v_off,
                        blay.slot_lay, blay.coeff_lay, beta_sorted,
                        block_n=blay.block_n, block_t=blay.block_t,
                        interpret=interpret)
        rows = jnp.arange(idx.slot.shape[0])[:, None]
        return jnp.mean(out[rows, blay.inv_pos], axis=0)

    differ("fused MXU vs fused",
           probe("fused matvec MXU HIGHEST k=1",
                 _on_index(mxu_matvec, index), beta), y)
    differ("reference vs fused",
           probe("reference segment-sum matvec",
                 _on_index(ref.matvec, index), beta), y)
    beta4 = jax.random.normal(jax.random.PRNGKey(4), (n, 4), jnp.float32)
    probe("fused matvec k=4", _on_index(pal.matvec, index), beta4)
    split = _on_index(lambda idx, b: pal.readout(idx, pal.loads(idx, b)),
                      index)
    differ("blocked split vs fused",
           probe("blocked split loads + readout k=1", split, beta), y)

    tables = _on_index(pal.loads, index)(beta)
    q = pal.featurize_buckets(xte[:QUERIES])
    vals = probe("xla row gather 1024 q",
                 jax.jit(lambda s, t: jnp.take_along_axis(t, s, axis=1)),
                 q.slot, tables)
    differ("serving readout vs xla row gather",
           probe("serving readout (row gather) 1024 q",
                 jax.jit(lambda t: bin_readout_op(q, t, interpret=interpret)),
                 tables),
           jnp.mean(vals * q.coeff, axis=0))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=1)
    return results


if __name__ == "__main__":
    main()
