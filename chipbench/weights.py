"""The served model's tables, made on the device from the seed.

A serving cell needs a fitted model's CountSketch tables at the
configuration's size, not a fit: the tables are the loads of a weight
vector beta over the training points,

    tables[s, b] = sum_{i: slot[s,i] = b} sign[s,i] * beta_i,

which is what a fit's last step writes.  Drawing beta from the seed gives
the tables the occupancy of a fitted model over the same points and keeps
set-up short.  One jitted call hashes the training points (``reference``'s
device hash) and scatters beta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import reference

BETA_SCALE = 1e-3       # the size of a fitted beta at lam = 1e-3·n


@functools.partial(jax.jit, static_argnames=("table_size",))
def make_tables(lsh, x, key, *, table_size: int):
    """(m, B) float32 loads of beta ~ BETA_SCALE·N(0, 1) over ``x``."""
    beta = BETA_SCALE * jax.random.normal(key, (x.shape[0],), jnp.float32)
    slot, sign = reference.hash_device(lsh, x, table_size)
    return jax.vmap(lambda s, g: jax.ops.segment_sum(
        g * beta, s, num_segments=table_size))(slot, sign)
