"""Pallas TPU kernel: fused WLSH featurization (hash + weight + sign).

The naive jnp path (repro.core.lsh.featurize) materializes six (m, n, d)
intermediates in HBM; at production scale (n = 4M, m = 64, d = 64) that is
~100 GB of traffic for a computation whose true output is 4 * (m, n) vectors.
This kernel fuses the whole per-(instance, point-block) pipeline in VMEM:

    t = (x - z) / w;  h = round(t);  u = h - t
    weight = prod_d f(u_d)          (closed-form piecewise polynomial f)
    key1/key2 = fmix32(sum_d uint32(h_d) * r_d)   (universal hashes)
    sign = 1 - 2*(key2 >> 31)

Layout: points lie along lanes and feature dims along sublanes — the kernel
reads ``x`` transposed as a (d_pad, BLOCK_N) tile, so ``d`` pads only to the
8-sublane boundary and every per-point result is a lane-dense (1, BLOCK_N)
row.  Grid: (n / BLOCK_N, m / GROUP) with the instance groups innermost, so
the point tile stays resident while GROUP instance rows of each output are
filled per step.  Feature dims beyond the real d are masked (weight factor
1, hash contribution 0), and instances beyond the real m (GROUP padding)
are trimmed by the wrapper.

TPU lowering notes: Mosaic has no product reduction and no unsigned
reductions or unsigned->float casts, so the product and the hash sums fold
rows explicitly, and the hash arithmetic runs in int32 — two's-complement
multiply/add/xor/logical-shift give exactly the uint32 wraparound bits —
with the keys bitcast back to uint32 outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bucket_fns import BucketFn

BLOCK_N = 1024
GROUP = 8            # instances per grid step: one sublane tile of output rows

# murmur3 fmix32 multipliers as int32 bit patterns
_C1 = 0x85EB_CA6B - (1 << 32)
_C2 = 0xC2B2_AE35 - (1 << 32)


def _fmix32(x):
    """murmur3 finalizer on int32 bit patterns (== the uint32 version)."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, 16)
    x = x * jnp.int32(_C1)
    x = x ^ srl(x, 13)
    x = x * jnp.int32(_C2)
    x = x ^ srl(x, 16)
    return x


def _fold_rows(a, op):
    """Reduce a (rows, bn) value over its rows with ``op`` -> (1, bn).
    rows is a multiple of 8: whole sublane tiles combine first, then the
    last tile folds in halves."""
    acc = a[0:8]
    for r in range(8, a.shape[0], 8):
        acc = op(acc, a[r:r + 8])
    for half in (4, 2, 1):
        acc = op(acc[:half], acc[half:2 * half])
    return acc


def _featurize_body(x_ref, w_ref, z_ref, r1_ref, r2_ref,
                    key1_ref, key2_ref, wt_ref, sign_ref, *, f: BucketFn,
                    d_real: int):
    x = x_ref[...]                               # (dp, bn) f32
    valid = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < d_real
    for g in range(w_ref.shape[0]):              # GROUP instances, static
        t = (x - z_ref[g]) / w_ref[g]            # params (dp, 1) per instance
        h = jnp.round(t)
        u = h - t                                # residual in [-1/2, 1/2]
        weight = _fold_rows(jnp.where(valid, f(u), 1.0), jnp.multiply)
        hi = jnp.where(valid, h, 0.0).astype(jnp.int32)
        k1 = _fmix32(_fold_rows(hi * r1_ref[g], jnp.add))
        k2 = _fmix32(_fold_rows(hi * r2_ref[g], jnp.add))
        key1_ref[g:g + 1, :] = k1
        key2_ref[g:g + 1, :] = k2
        wt_ref[g:g + 1, :] = weight.astype(jnp.float32)
        sign_ref[g:g + 1, :] = 1.0 - 2.0 * jax.lax.shift_right_logical(
            k2, 31).astype(jnp.float32)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(jax.jit, static_argnames=("f", "interpret", "block_n"))
def featurize_pallas(x, w, z, r1, r2, *, f: BucketFn, interpret: bool,
                     block_n: int = BLOCK_N):
    """x (n, d) f32; w, z (m, d) f32; r1, r2 (m, d) uint32.
    Returns (key1, key2, weight, sign), each (m, n).  ``interpret`` runs the
    kernel in the Pallas interpreter (CPU only) instead of compiling it."""
    n, d = x.shape
    m = w.shape[0]
    dp = _round_up(d, 8)
    mp = _round_up(m, GROUP)
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n={n} must be a multiple of block_n={bn}")

    xt = jnp.pad(x.astype(jnp.float32).T, ((0, dp - d), (0, 0)))

    def column(a, fill):                                 # (m, d) -> (mp, dp, 1)
        return jnp.pad(a, ((0, mp - m), (0, dp - d)),
                       constant_values=fill)[:, :, None]

    def hash_bits(r):
        return jax.lax.bitcast_convert_type(r.astype(jnp.uint32), jnp.int32)

    wp = column(w.astype(jnp.float32), 1.0)
    zp = column(z.astype(jnp.float32), 0.0)
    r1p = column(hash_bits(r1), 0)
    r2p = column(hash_bits(r2), 0)

    grid = (n // bn, mp // GROUP)
    point_spec = pl.BlockSpec((dp, bn), lambda j, g: (0, j))
    inst_spec = pl.BlockSpec((GROUP, dp, 1), lambda j, g: (g, 0, 0))
    out_spec = pl.BlockSpec((GROUP, bn), lambda j, g: (g, j))

    out_shapes = (
        jax.ShapeDtypeStruct((mp, n), jnp.int32),
        jax.ShapeDtypeStruct((mp, n), jnp.int32),
        jax.ShapeDtypeStruct((mp, n), jnp.float32),
        jax.ShapeDtypeStruct((mp, n), jnp.float32),
    )
    k1, k2, wt, sg = pl.pallas_call(
        functools.partial(_featurize_body, f=f, d_real=d),
        grid=grid,
        in_specs=[point_spec, inst_spec, inst_spec, inst_spec, inst_spec],
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        out_shape=out_shapes,
        interpret=interpret,
        name="wlsh_featurize",
    )(xt, wp, zp, r1p, r2p)
    as_u32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.uint32)
    return as_u32(k1[:m]), as_u32(k2[:m]), wt[:m], sg[:m]
