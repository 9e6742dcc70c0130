"""Data of the benchmark's configurations, made from the seed.

A copy of the synthetic stand-ins for the paper's Table-2 regression sets
(``repro.data.regression``) and of the median-L1 lengthscale heuristic
(``chip_smoke.py``), kept here so that a change to the program cannot move
what the benchmark feeds it.  The UCI files are not in the repository: the
stand-ins keep each set's published d and n, with targets that mix a smooth
and a kinked component.

    x ~ U[0, 2]^d,  y = (1 - rough)·smooth(x) + rough·kinks(x) + 0.1·N(0, 1),
    y standardised over the training rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NOISE = 0.1
LENGTHSCALE_SAMPLE = 256


def _target(key, x, rough: float):
    d = x.shape[-1]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w_s = jax.random.normal(k1, (d, 16)) / jnp.sqrt(d)
    b_s = jax.random.uniform(k2, (16,), maxval=2 * jnp.pi)
    smooth = jnp.cos(x @ w_s + b_s) @ jnp.ones((16,)) / 4.0
    w_r = jax.random.normal(k3, (d, 8)) / jnp.sqrt(d)
    b_r = jax.random.normal(k4, (8,)) * 0.3
    kinks = jnp.abs(x @ w_r - b_r) @ jnp.ones((8,)) / 8.0
    return (1.0 - rough) * smooth + rough * kinks


@functools.partial(jax.jit, static_argnames=("d", "n_train", "n_test"))
def regression_set(key, *, d: int, n_train: int, n_test: int, rough):
    """(x_train, y_train, x_test, y_test) for PRNG ``key``, made on the
    default device in one call."""
    kx, kt, kn1, _ = jax.random.split(key, 4)
    x = jax.random.uniform(kx, (n_train + n_test, d)) * 2.0
    y = _target(kt, x, rough)
    y = y + NOISE * jax.random.normal(kn1, y.shape)
    mu, sd = jnp.mean(y[:n_train]), jnp.std(y[:n_train]) + 1e-9
    y = (y - mu) / sd
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


@functools.partial(jax.jit, static_argnames=("d", "n"))
def _points(key, *, d: int, n: int):
    return jax.random.uniform(key, (n, d)) * 2.0


def train_points(cfg: dict, key):
    """Only the training rows x ~ U[0, 2]^d of the configuration, for
    ``key``: what a served model's tables are made over."""
    return _points(key, d=int(cfg["d"]), n=int(cfg["n_train"]))


def make_set(cfg: dict, key):
    """The configuration's data set for ``key`` (a PRNG key)."""
    return regression_set(key, d=int(cfg["d"]), n_train=int(cfg["n_train"]),
                          n_test=int(cfg["n_test"]),
                          rough=float(cfg["data"]["rough"]))


def lengthscale(x) -> float:
    """Median-heuristic L1 lengthscale: half the median pairwise L1
    distance of a fixed 256-point subsample."""
    x = np.asarray(x)
    xs = x[np.random.default_rng(0).choice(x.shape[0], LENGTHSCALE_SAMPLE,
                                           replace=False)]
    return float(np.median(np.abs(xs[:, None] - xs[None]).sum(-1))) / 2.0
