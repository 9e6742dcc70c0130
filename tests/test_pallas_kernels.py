"""Pallas kernel validation (interpret=True): shape/dtype sweeps against the
pure-jnp oracles, per the kernels/<name>/{kernel,ops,ref}.py contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GammaPDF, get_bucket_fn, make_operator, \
    sample_lsh_params
from repro.core.lsh import featurize as featurize_jnp
from repro.core.wlsh import build_blocked_layout, build_table_index, \
    table_matvec
from repro.kernels.binning import (bin_gather_blocked_pallas, bin_gather_ref,
                                   bin_scatter_blocked_pallas,
                                   bin_scatter_ref)
from repro.kernels.featurize import featurize_op
from repro.kernels.flash_decode import flash_decode_pallas, flash_decode_ref


# ---------------------------------------------------------------------------
# featurize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,m", [(128, 1, 1), (300, 5, 3), (512, 11, 2),
                                   (257, 64, 1), (96, 200, 1)])
@pytest.mark.parametrize("fname", ["rect", "tent", "smooth"])
def test_featurize_kernel_matches_ref(n, d, m, fname):
    key = jax.random.PRNGKey(n + d + m)
    x = jax.random.uniform(key, (n, d)) * 4.0 - 2.0
    params = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                               GammaPDF(2.0, 1.0))
    f = get_bucket_fn(fname)
    ref = featurize_jnp(params, f, x)
    out = featurize_op(params, f, x, interpret=True)
    assert bool(jnp.all(out.key1 == ref.key1))
    assert bool(jnp.all(out.key2 == ref.key2))
    np.testing.assert_allclose(out.weight, ref.weight, atol=2e-6)
    assert bool(jnp.all(out.sign == ref.sign))


def test_featurize_kernel_f32_input_dtypes():
    key = jax.random.PRNGKey(0)
    x64 = np.random.RandomState(0).uniform(size=(256, 3)) * 2.0  # f64 numpy
    params = sample_lsh_params(key, 2, 3, GammaPDF(2.0, 1.0))
    f = get_bucket_fn("rect")
    out = featurize_op(params, f, jnp.asarray(x64), interpret=True)
    ref = featurize_jnp(params, f, jnp.asarray(x64, jnp.float32))
    assert bool(jnp.all(out.key1 == ref.key1))


# ---------------------------------------------------------------------------
# binning (scatter / gather as one-hot reductions over visit lists)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,b", [(1, 128, 512), (3, 1024, 1024),
                                   (2, 256, 2048), (2, 1024, 512)])
def test_bin_scatter_gather_match_ref(m, n, b):
    """The visit-list scatter and gather over the slot-blocked layout of
    raw (slot, contrib) arrays match the oracles: contributions enter along
    the layout (padding positions carry 0), the gather comes back to point
    order through ``inv_pos``."""
    key = jax.random.PRNGKey(m * n)
    slot = jax.random.randint(key, (m, n), 0, b, dtype=jnp.int32)
    contrib = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    lay = build_blocked_layout(slot, contrib, b, parts="pallas")
    padded = jnp.concatenate([contrib, jnp.zeros((m, 1))], axis=1)
    contrib_lay = jnp.take_along_axis(padded, lay.src, axis=1)
    t_k = bin_scatter_blocked_pallas(
        lay.vs_block, lay.vs_tile, lay.slot_lay, contrib_lay,
        num_tiles=lay.num_tiles, block_n=lay.block_n, block_t=lay.block_t,
        interpret=True)[:, :b]
    t_r = bin_scatter_ref(slot, contrib, table_size=b)
    np.testing.assert_allclose(t_k, t_r, atol=1e-4)
    g_lay = bin_gather_blocked_pallas(lay.vg_tile, lay.slot_lay, t_k,
                                      block_n=lay.block_n,
                                      block_t=lay.block_t, interpret=True)
    g_k = jnp.take_along_axis(g_lay, lay.inv_pos, axis=1)
    np.testing.assert_allclose(g_k, bin_gather_ref(slot, t_r), atol=1e-4)


def test_table_matvec_op_matches_core(rng):
    """A Pallas operator's split readout ∘ loads on a slot-blocked index
    (the visit-list kernels) is the reference table matvec."""
    n, d, m, b = 300, 3, 6, 1024
    x = jax.random.uniform(rng, (n, d)) * 2.0
    params = sample_lsh_params(jax.random.fold_in(rng, 1), m, d,
                               GammaPDF(2.0, 1.0))
    f = get_bucket_fn("rect")
    feats = featurize_jnp(params, f, x)
    op = make_operator(params, f, b, backend="pallas")
    bidx = op.build_index(feats)
    assert bidx.blocked is not None
    beta = jax.random.normal(jax.random.fold_in(rng, 2), (n,))
    np.testing.assert_allclose(op.readout(bidx, op.loads(bidx, beta)),
                               table_matvec(build_table_index(feats, b), beta),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,kv,g,d,t", [(1, 1, 1, 64, 256), (2, 2, 3, 64, 512),
                                        (2, 4, 1, 128, 256), (2, 1, 4, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(b, kv, g, d, t, dtype):
    key = jax.random.PRNGKey(b * t + d)
    q = jax.random.normal(key, (b, kv, g, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, d)).astype(dtype)
    lens = jax.random.randint(jax.random.fold_in(key, 3), (b, 1), 1, t + 1)
    valid = (jnp.arange(t)[None, :] < lens).astype(jnp.int32)
    out_k = flash_decode_pallas(q, k, v, valid, interpret=True, block_t=256)
    out_r = flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(out_k, out_r, atol=3e-6 if dtype == jnp.float32
                               else 3e-3)


def test_flash_decode_single_valid_row():
    """Degenerate mask (one valid key) must return exactly that value row."""
    b, kv, g, d, t = 2, 1, 2, 32, 128
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (b, kv, g, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, d))
    valid = jnp.zeros((b, t), jnp.int32).at[:, 0].set(1)
    out = flash_decode_pallas(q, k, v, valid, interpret=True, block_t=64)
    np.testing.assert_allclose(out, jnp.broadcast_to(
        v[:, 0][:, :, None, :], out.shape), atol=1e-5)
