"""Fused one-pass CountSketch matvec (slot-blocked layout).

Pins the PR's acceptance criteria: parity with the split reference path
(<= 1e-5, including odd n / non-dividing tile sizes / m=1 / zero weights),
the O(n) tile-visit schedule (vs the old (n/bn)·(B/bt) cross product), the
HBM residency claim (the (m, B) table exists in the split program's HLO but
never in the fused one), bitwise stability of the solver across the fused
toggle on the reference backend, and the CG atol floor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GammaPDF, WLSHKernelSpec, cg_solve, get_bucket_fn,
                        make_operator, sample_lsh_params, wlsh_krr_fit)
from repro.core.wlsh import (build_blocked_layout, build_table_index,
                             table_matvec, table_matvec_fused)
from repro.hlo_analysis import materializes_shape
from repro.kernels.binning import bin_fused_matvec_op


def _setup(key, n, d, m, table_size, bucket="rect"):
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    f = get_bucket_fn(bucket)
    split = make_operator(lsh, f, table_size, backend="reference", fused=False)
    fused_ref = make_operator(lsh, f, table_size, backend="reference")
    fused_pal = make_operator(lsh, f, table_size, backend="pallas")
    feats = split.featurize(x)
    sidx = split.build_index(feats)
    # each backend's build_index materializes only its own layout group
    fidx = fused_ref.build_index(feats)
    fidx_pal = fused_pal.build_index(feats)
    return beta, split, fused_ref, fused_pal, sidx, fidx, fidx_pal


# odd n, n < block_n, m=1, table sizes from one tile up — all padding paths
@pytest.mark.parametrize("n,d,m,table_size", [(97, 3, 2, 512),
                                              (300, 5, 4, 1024),
                                              (128, 2, 1, 256),
                                              (257, 3, 3, 2048)])
def test_fused_matvec_parity(n, d, m, table_size):
    key = jax.random.PRNGKey(n + d + m)
    beta, split, fused_ref, fused_pal, sidx, fidx, fidx_pal = \
        _setup(key, n, d, m, table_size)
    assert sidx.blocked is None and fidx.blocked is not None
    want = split.matvec(sidx, beta)
    got_ref = fused_ref.matvec(fidx, beta)
    got_pal = fused_pal.matvec(fidx_pal, beta)
    np.testing.assert_allclose(got_ref, want, atol=1e-5)
    np.testing.assert_allclose(got_pal, want, atol=1e-5)
    # sum mode (the distributed model-axis contribution) must agree too
    want_sum = split.matvec(sidx, beta, average=False)
    np.testing.assert_allclose(fused_ref.matvec(fidx, beta, average=False),
                               want_sum, atol=1e-4)
    np.testing.assert_allclose(fused_pal.matvec(fidx_pal, beta, average=False),
                               want_sum, atol=1e-4)


def test_fused_kernel_odd_tile_size():
    """table_size not divisible by block_t: the tile grid covers
    ceil(B / bt) tiles and the trailing partial tile just stays sparse."""
    key = jax.random.PRNGKey(11)
    n, d, m, table_size = 200, 3, 3, 1024
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    feats = make_operator(lsh, get_bucket_fn("rect"), table_size,
                          backend="reference").featurize(x)
    idx = build_table_index(feats, table_size)
    # 384 does not divide 1024 -> 3 tiles covering [0, 1152)
    lay = build_blocked_layout(idx.slot, idx.coeff, table_size,
                               block_n=128, block_t=384)
    idx = idx._replace(blocked=lay)
    want = table_matvec(idx, beta)
    np.testing.assert_allclose(bin_fused_matvec_op(idx, beta, interpret=True),
                               want, atol=1e-5)
    np.testing.assert_allclose(table_matvec_fused(idx, beta), want, atol=1e-5)


def test_fused_matvec_all_zero_weights():
    """coeff = 0 everywhere -> the matvec is exactly zero on every path."""
    key = jax.random.PRNGKey(5)
    n, d, m, table_size = 130, 2, 2, 512
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="reference")
    feats = op.featurize(x)
    feats = feats._replace(weight=jnp.zeros_like(feats.weight))
    idx = build_table_index(feats, table_size)
    idx = idx._replace(blocked=build_blocked_layout(idx.slot, idx.coeff,
                                                    table_size))
    assert bool(jnp.all(table_matvec_fused(idx, beta) == 0.0))
    assert bool(jnp.all(bin_fused_matvec_op(idx, beta, interpret=True) == 0.0))


def test_blocked_layout_schedules_O_n_tiles():
    """The visit schedule is O(n/bn + B/bt) per instance — linear in n when
    B = Θ(n) — not the (n/bn)·(B/bt) cross product the split grid iterates."""
    key = jax.random.PRNGKey(3)
    n, d, m, table_size = 8192, 4, 4, 32768
    bn, bt = 128, 512
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="reference")
    idx = op.build_index(op.featurize(x))
    lay = build_blocked_layout(idx.slot, idx.coeff, table_size,
                               block_n=bn, block_t=bt, parts="pallas")
    n_tiles = table_size // bt
    bound = 2 * (n // bn + n_tiles)          # scatter + gather passes
    assert lay.v_block.shape[1] == bound      # static grid is already O(n)
    assert int(jnp.max(lay.n_visits)) <= bound
    cross_product = (n // bn) * n_tiles       # split-kernel visits/instance
    assert bound < cross_product / 4
    # doubling n (with B = 4n) must double the schedule, not quadruple it:
    # build the 2n layout for real and compare static and measured visits
    x2 = jax.random.uniform(jax.random.fold_in(key, 9), (2 * n, d)) * 2.0
    op2 = make_operator(lsh, get_bucket_fn("rect"), 2 * table_size,
                        backend="reference")
    idx2 = op2.build_index(op2.featurize(x2), blocked=False)
    lay2 = build_blocked_layout(idx2.slot, idx2.coeff, 2 * table_size,
                                block_n=bn, block_t=bt, parts="pallas")
    assert lay2.v_block.shape[1] == 2 * bound
    assert int(jnp.max(lay2.n_visits)) <= 2 * bound
    # the cross product would have quadrupled
    assert (2 * n // bn) * (2 * table_size // bt) == 4 * cross_product


def test_fused_matvec_table_never_materialized_to_hbm():
    """Acceptance criterion: the (m, B) table appears in the split program's
    HLO (scatter output round-trips through HBM into the gather) but never
    in the fused program (VMEM scratch tile only)."""
    key = jax.random.PRNGKey(7)
    n, d, m, table_size = 300, 3, 4, 1024
    beta, split, fused_ref, fused_pal, sidx, fidx, fidx_pal = \
        _setup(key, n, d, m, table_size)
    pal_split = make_operator(split.lsh, split.bucket, table_size,
                              backend="pallas", fused=False)
    for op_split, op_fused, idx in ((split, fused_ref, fidx),
                                    (pal_split, fused_pal, fidx_pal)):
        hlo_split = jax.jit(lambda b: op_split.matvec(sidx, b)) \
            .lower(beta).compile().as_text()
        hlo_fused = jax.jit(lambda b: op_fused.matvec(idx, b)) \
            .lower(beta).compile().as_text()
        # the kernels carry the table with a unit row axis, (m, 1, B)
        shapes = ((m, table_size), (m, 1, table_size))
        assert any(materializes_shape(hlo_split, s) for s in shapes)
        assert not any(materializes_shape(hlo_fused, s) for s in shapes)


def test_wlsh_krr_fit_bitwise_stable_across_fused_toggle():
    """Acceptance criterion: fused vs split solve on the reference backend
    produces bitwise-identical (beta, tables) — the stable slot sort keeps
    every bucket's contributions in the same addition order."""
    key = jax.random.PRNGKey(0)
    n, d = 300, 3
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    fit = lambda fused: wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec,
                                     m=16, lam=0.5, maxiter=60,
                                     backend="reference", fused=fused)
    m_fused, m_split = fit(True), fit(False)
    np.testing.assert_array_equal(np.asarray(m_fused.beta),
                                  np.asarray(m_split.beta))
    np.testing.assert_array_equal(np.asarray(m_fused.tables),
                                  np.asarray(m_split.tables))
    assert int(m_fused.cg_iters) == int(m_split.cg_iters)


def test_distributed_fused_local_matvec_single_data_shard():
    """Data axes of size 1: make_krr_step takes the fused local-matvec branch
    (no table psum needed) and must be bitwise-equal to the split step —
    same guarantee as the single-host fused toggle."""
    from repro.compat import make_mesh
    from repro.core.distributed import KRRStepConfig, make_krr_step
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    n, d, m, table_size = 192, 3, 4, 512
    key = jax.random.PRNGKey(6)
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    lsh = sample_lsh_params(jax.random.fold_in(key, 2), m, d,
                            GammaPDF(2.0, 1.0))
    f = get_bucket_fn("rect")
    cfg_fused = KRRStepConfig(m=m, table_size=table_size, lam=0.5,
                              cg_iters=15, data_axes=("pod", "data"),
                              model_axis="model", backend="reference",
                              fused=True)
    cfg_split = cfg_fused._replace(fused=False)
    b_f, r_f, t_f = jax.jit(make_krr_step(mesh, cfg_fused, f))(x, y, lsh)
    b_s, r_s, t_s = jax.jit(make_krr_step(mesh, cfg_split, f))(x, y, lsh)
    np.testing.assert_array_equal(np.asarray(b_f), np.asarray(b_s))
    np.testing.assert_array_equal(np.asarray(t_f), np.asarray(t_s))
    assert float(r_f) == float(r_s)


def test_cg_zero_rhs_terminates_immediately():
    """atol floor: b = 0 must not loop maxiter times on thresh = 0."""
    res = cg_solve(lambda v: v, jnp.zeros((16,), jnp.float32), lam=1.0)
    assert int(res.iters) == 0
    assert float(res.resnorm) == 0.0


def test_wlsh_krr_fit_exposes_tol_atol():
    """tol/atol thread through to cg_solve: an all-zero target terminates in
    zero iterations (atol floor), and a loose tol stops earlier than a
    tight one."""
    key = jax.random.PRNGKey(4)
    n, d = 200, 2
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    zero = wlsh_krr_fit(jax.random.fold_in(key, 2), x, jnp.zeros_like(y),
                        spec, m=8, lam=0.5, backend="reference")
    assert int(zero.cg_iters) == 0
    loose = wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec, m=8,
                         lam=0.5, tol=1e-2, backend="reference")
    tight = wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec, m=8,
                         lam=0.5, tol=1e-7, atol=0.0, backend="reference")
    assert int(loose.cg_iters) < int(tight.cg_iters)
