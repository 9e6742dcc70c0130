"""Plain reference of the WLSH kernel ridge regression the cells run.

It imports nothing of the program.  Everything here follows the paper's
Def. 5-6 with the rect bucket and the CountSketch table of the
configuration, written out directly:

    t = (x - z) / w,  h = round(t)                    (f32, IEEE)
    key_j = fmix32(sum_d uint32(h_d) * r_j,d)          (uint32 wraparound)
    slot = key1 mod B,  sign = 1 - 2 * (key2 >> 31),  weight = 1 (rect)
    loads[s, b] = sum_{i: slot[s,i] = b} sign[s,i] * beta_i
    (K~ beta)_i = (1/m) sum_s sign[s,i] * loads[s, slot[s,i]]

Hashes are computed in jnp on the device, one instance at a time; loads
and readouts in float64 on the host.  ``HASH_MARGIN`` marks the
(instance, point) pairs whose bucket coordinate lies so close to a
rounding boundary that a division rounded a few ulp differently (the
TPU's is not IEEE's, and the program's kernel divides its own way) may put
the point in the neighbouring bucket: the checks treat such a pair as
either bucket.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

# |t - h| this close to 1/2, relative to max(|t|, 1), may round either way
# under a division that is off by a few ulp (8 ulp of values in [1/2, 1))
HASH_MARGIN = 2.0 ** -21


def sample_lsh(key, m: int, d: int, gamma_shape: float, gamma_scale: float,
               lengthscale: float):
    """m LSH instances over R^d drawn from ``key``: widths w ~ Gamma scaled
    by the lengthscale, offsets z ~ U[0, w], odd 32-bit hash multipliers."""
    kw, kz, k1, k2 = jax.random.split(key, 4)
    w = jax.random.gamma(kw, gamma_shape, (m, d), dtype=jnp.float32)
    w = w * gamma_scale * jnp.asarray(lengthscale, jnp.float32)
    z = jax.random.uniform(kz, (m, d), dtype=jnp.float32) * w
    top = jnp.iinfo(jnp.int32).max
    r1 = jax.random.randint(k1, (m, d), 0, top, dtype=jnp.int32)
    r2 = jax.random.randint(k2, (m, d), 0, top, dtype=jnp.int32)
    r1 = (r1.astype(jnp.uint32) << 1) | jnp.uint32(1)
    r2 = (r2.astype(jnp.uint32) << 1) | jnp.uint32(1)
    return w, z, r1, r2


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EB_CA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2_AE35)
    return x ^ (x >> 16)


def _bucket(h, r1, r2, table_size: int):
    hi = h.astype(jnp.int32).astype(jnp.uint32)
    k1 = _fmix32(jnp.sum(hi * r1, axis=-1, dtype=jnp.uint32))
    k2 = _fmix32(jnp.sum(hi * r2, axis=-1, dtype=jnp.uint32))
    slot = (k1 & jnp.uint32(table_size - 1)).astype(jnp.int32)
    return slot, 1.0 - 2.0 * (k2 >> 31).astype(jnp.float32)


def hash_device(lsh, x, table_size: int, *, margin: bool = False):
    """(slot int32, sign f32), each (m, n), of every row of ``x`` under
    every instance, one instance at a time on the default device.  With
    ``margin`` also ``near`` (bool: a coordinate within HASH_MARGIN of a
    rounding boundary) and the bucket with all such coordinates rounded
    the other way (``alt_slot``, ``alt_sign``)."""
    w, z, r1, r2 = lsh

    def one(args):
        w_s, z_s, r1_s, r2_s = args
        t = (x - z_s) / w_s
        h = jnp.round(t)
        slot, sign = _bucket(h, r1_s, r2_s, table_size)
        if not margin:
            return slot, sign
        near_c = jnp.abs(t - h) >= 0.5 - HASH_MARGIN * jnp.maximum(
            jnp.abs(t), 1.0)
        h_alt = jnp.where(near_c, h + jnp.where(t > h, 1.0, -1.0), h)
        return (slot, sign, jnp.any(near_c, axis=-1)) + _bucket(
            h_alt, r1_s, r2_s, table_size)

    return jax.lax.map(one, (w, z, r1, r2))


_hash_with_margin = jax.jit(functools.partial(hash_device, margin=True),
                            static_argnames=("table_size",))


def hash_points(lsh, x, table_size: int):
    """Host copies of (slot, sign, near, alt_slot, alt_sign), each (m, n);
    slots int64, signs float64."""
    slot, sign, near, alt_slot, alt_sign = _hash_with_margin(
        lsh, jnp.asarray(x, jnp.float32), table_size=table_size)
    return (np.asarray(slot, np.int64), np.asarray(sign, np.float64),
            np.asarray(near), np.asarray(alt_slot, np.int64),
            np.asarray(alt_sign, np.float64))


def loads(slot, sign, beta, table_size: int):
    """(m, B) float64 CountSketch loads of ``beta`` (n,)."""
    beta = np.asarray(beta, np.float64)
    return np.stack([np.bincount(slot[s], weights=sign[s] * beta,
                                 minlength=table_size)
                     for s in range(slot.shape[0])])


def readout(slot, sign, tables):
    """(n,) float64 readout (1/m) sum_s sign * tables[s, slot]."""
    m = slot.shape[0]
    t = np.asarray(tables)
    vals = t[np.arange(m)[:, None], slot].astype(np.float64)
    return (sign * vals).mean(axis=0)


# -- checks -----------------------------------------------------------------

def fit_gaps(hashes, x_beta, y, lam: float, tables, table_size: int):
    """How far one fitted model (its beta and its (m, B) tables) is from
    solving the reference system (K~ + lam I) beta = y.

    ``residual``: ||(1/m) sum_s sign * tables[s, slot] + lam beta - y|| /
    ||y|| over the points none of whose buckets is in doubt — the relative
    residual of the solve, read through the model's own tables, so it also
    holds the tables to being the loads of beta.  ``tables``: ||tables -
    loads(beta)|| / ||loads(beta)|| over the slots that no point in doubt
    can reach.  Both are 0 for an exact solve in exact arithmetic."""
    slot, sign, near, alt_slot, _ = hashes
    beta = np.asarray(x_beta, np.float64)
    y = np.asarray(y, np.float64)
    t_prog = np.asarray(tables, np.float64)
    kb = readout(slot, sign, t_prog)
    ok = ~near.any(axis=0)
    r = (kb + lam * beta - y)[ok]
    residual = float(np.linalg.norm(r) / max(np.linalg.norm(y[ok]), 1e-30))
    t_ref = loads(slot, sign, beta, table_size)
    doubt = np.zeros_like(t_ref, bool)
    rows = np.broadcast_to(np.arange(slot.shape[0])[:, None], slot.shape)
    doubt[rows[near], slot[near]] = True
    doubt[rows[near], alt_slot[near]] = True
    gap = np.linalg.norm((t_prog - t_ref)[~doubt])
    return {"residual": residual,
            "tables": float(gap / max(np.linalg.norm(t_ref[~doubt]), 1e-30)),
            "points_in_doubt": int((~ok).sum())}


def answer_gaps(hashes, tables, served):
    """Per-query |served - reference| for answers read out of ``tables``
    (the benchmark's own), where a query whose bucket is in doubt in some
    instances may take either bucket there: its gap is the least over
    those choices."""
    slot, sign, near, alt_slot, alt_sign = hashes
    m = slot.shape[0]
    inst = np.arange(m)[:, None]
    # index the (possibly device-resident) tables, never copy them whole
    base = sign * np.asarray(tables[inst, slot], np.float64)
    alt = alt_sign * np.asarray(tables[inst, alt_slot], np.float64)
    ref = base.sum(axis=0) / m
    served = np.asarray(served, np.float64)
    gap = np.abs(served - ref)
    for q in np.flatnonzero(near.any(axis=0)):
        deltas = ((alt - base)[near[:, q], q] / m)[:8]
        sums = [sum(c) for k in range(len(deltas) + 1)
                for c in itertools.combinations(deltas, k)]
        gap[q] = min(abs(served[q] - ref[q] - s) for s in sums)
    return gap, ref
