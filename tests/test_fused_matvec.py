"""Fused one-pass CountSketch matvec (slot-blocked layout).

Pins parity with the split reference path (<= 1e-5, including odd n /
non-dividing tile sizes / m=1 / zero weights), the O(n) tile-visit schedule
(vs the (n/bn)·(B/bt) cross product), the HBM residency claim (the (m, B)
table exists in the split program's HLO but never in the fused one),
bitwise agreement of the fused solve with a split solve on the reference
backend, and the CG atol floor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (GammaPDF, WLSHKernelSpec, cg_solve, get_bucket_fn,
                        make_operator, pcg_solve, sample_lsh_params,
                        wlsh_krr_fit)
from repro.core.wlsh import (TableIndex, build_blocked_layout,
                             build_table_index, table_loads, table_matvec,
                             table_matvec_fused, table_readout)
from repro.hlo_analysis import materializes_shape
from repro.kernels.binning import bin_fused_matvec_op
from repro.kernels.binning import kernel as kmod


def _setup(key, n, d, m, table_size, bucket="rect"):
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    f = get_bucket_fn(bucket)
    fused_ref = make_operator(lsh, f, table_size, backend="reference")
    fused_pal = make_operator(lsh, f, table_size, backend="pallas")
    feats = fused_ref.featurize(x)
    sidx = build_table_index(feats, table_size)     # split: no layout
    # each backend's build_index materializes only its own layout group
    fidx = fused_ref.build_index(feats)
    fidx_pal = fused_pal.build_index(feats)
    return beta, fused_ref, fused_pal, sidx, fidx, fidx_pal


# odd n, n < block_n, m=1, table sizes from one tile up — all padding paths
@pytest.mark.parametrize("n,d,m,table_size", [(97, 3, 2, 512),
                                              (300, 5, 4, 1024),
                                              (128, 2, 1, 256),
                                              (257, 3, 3, 2048)])
def test_fused_matvec_parity(n, d, m, table_size):
    key = jax.random.PRNGKey(n + d + m)
    beta, fused_ref, fused_pal, sidx, fidx, fidx_pal = \
        _setup(key, n, d, m, table_size)
    assert sidx.blocked is None and fidx.blocked is not None
    want = table_matvec(sidx, beta)
    got_ref = fused_ref.matvec(fidx, beta)
    got_pal = fused_pal.matvec(fidx_pal, beta)
    np.testing.assert_allclose(got_ref, want, atol=1e-5)
    np.testing.assert_allclose(got_pal, want, atol=1e-5)
    # sum mode (the distributed model-axis contribution) must agree too
    want_sum = table_readout(sidx, table_loads(sidx, beta), average=False)
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


def _padded_body(v_block_ref, v_tile_ref, v_phase_ref, slot_ref, coeff_ref,
                 beta_ref, out_ref, table_ref):
    """The fused kernel's visit as it was when beta arrived along the padded
    layout: every visit reads layout block ``v_block`` of ``beta_ref``."""
    i, j = pl.program_id(0), pl.program_id(1)
    tile = v_tile_ref[i, j]
    phase = v_phase_ref[i, j]
    prev_tile = v_tile_ref[i, jnp.maximum(j - 1, 0)]

    @pl.when((j == 0) | (tile != prev_tile))
    def _zero():
        table_ref[...] = jnp.zeros_like(table_ref)

    hit = kmod._hit(slot_ref, tile, table_ref.shape[0])

    @pl.when(phase == 0)
    def _scatter_visit():
        cols = kmod._scatter(hit, coeff_ref[...] * beta_ref[...])
        for c, col in enumerate(cols):
            table_ref[:, c:c + 1] += col

    @pl.when(phase == 1)
    def _gather_visit():
        cols = [table_ref[:, c:c + 1] for c in range(table_ref.shape[1])]
        out_ref[...] = coeff_ref[...] * kmod._gather(hit, cols)


def _padded_matvec(index, beta, average):
    """The fused matvec off the padded layout: beta_pad[lay.src] (m·L
    values, padding reads an appended zero) into the kernel above, then
    the same gather back through ``inv_pos``."""
    lay = index.blocked
    pad = jnp.zeros((1,) + beta.shape[1:], jnp.float32)
    beta_lay = jnp.concatenate([beta, pad])[lay.src]
    if beta.ndim == 2:
        beta_lay = jnp.swapaxes(beta_lay, 1, 2)              # (m, k, L)
    beta3 = kmod._with_row_axis(beta_lay)
    k, bn = beta3.shape[1], lay.block_n

    def specs(s):
        def index_map(i, j, vb, vt, vp):
            return i + s, 0, vb[i, j]
        lay_spec = pl.BlockSpec((None, 1, bn), index_map)
        beta_spec = pl.BlockSpec((None, k, bn), index_map)
        return [lay_spec, lay_spec, beta_spec], beta_spec

    out = kmod._grouped_call(
        _padded_body,
        (lay.v_block, lay.v_tile_phase // 2, lay.v_tile_phase % 2),
        (kmod._with_row_axis(lay.slot_lay),
         kmod._with_row_axis(lay.coeff_lay), beta3), specs,
        jax.ShapeDtypeStruct(beta3.shape, jnp.float32),
        name="padded_fused_matvec", interpret=True,
        scratch_shapes=[pltpu.VMEM((lay.block_t, k), jnp.float32)])
    rows = jnp.arange(lay.v_block.shape[0])[:, None]
    vals = (jnp.swapaxes(out, 1, 2) if beta.ndim == 2 else out[:, 0])[
        rows, lay.inv_pos]
    return jnp.mean(vals, axis=0) if average else jnp.sum(vals, axis=0)


def _index_by_tile(counts, table_size, block_t, seed):
    """A table index whose instance s puts counts[s][t] points, at random
    slots, in table tile t: tiles with more than block_n points, empty
    tiles and a tile starting near the end of the sorted order on demand."""
    rng = np.random.default_rng(seed)
    slot = np.stack([
        rng.permutation(np.concatenate([
            rng.integers(t * block_t, (t + 1) * block_t, c)
            for t, c in enumerate(row)]))
        for row in counts]).astype(np.int32)
    coeff = rng.normal(size=slot.shape).astype(np.float32)
    idx = TableIndex(slot=jnp.asarray(slot), sign=jnp.asarray(np.sign(coeff)),
                     weight=jnp.asarray(np.abs(coeff)),
                     coeff=jnp.asarray(coeff), table_size=table_size)
    return idx._replace(blocked=build_blocked_layout(
        idx.slot, idx.coeff, table_size, block_t=block_t, parts="pallas"))


def _hashed_index(n, d, m, table_size):
    *_, fidx_pal = _setup(jax.random.PRNGKey(n + d + m), n, d, m, table_size)
    return fidx_pal


_SORTED_READ_CASES = {
    # tiles of 300-550 points (3-5 blocks), tiles 1-3 of 4 empty in turn,
    # n = 700 not a multiple of bn: the last block's +1 is clamped
    "multi_block_empty_tiles": lambda: _index_by_tile(
        [[400, 0, 300, 0], [0, 150, 0, 550], [700, 0, 0, 0]], 2048, 512, 0),
    # n = 256 = 2 blocks; tile 1 starts 200 points in: block 1's +1 clamps
    "clamp_at_aligned_end": lambda: _index_by_tile([[200, 56]], 1024, 512,
                                                   1),
    "hashed": lambda: _hashed_index(300, 5, 4, 1024),
}


@pytest.mark.parametrize("average", [True, False])
@pytest.mark.parametrize("k", [None, 1, 4])
@pytest.mark.parametrize("case", sorted(_SORTED_READ_CASES))
def test_fused_matvec_sorted_read_matches_padded_layout(case, k, average):
    """The fused kernel reads beta from each instance's sorted order (an
    m·n gather) instead of the padded layout (m·L): the same values reach
    the same lanes in the same order, so the matvec is bitwise the padded
    layout's.  Every case has scatter visits at an unaligned offset (the
    roll), and one whose next sorted block lies past the end (the clamp)."""
    index = _SORTED_READ_CASES[case]()
    lay = index.blocked
    n, bn = index.slot.shape[1], lay.block_n
    last = -(-n // bn) - 1
    scatter = lay.v_tile_phase % 2 == 0
    assert bool(jnp.any(scatter & (lay.v_off % bn != 0)))
    if case != "hashed":
        assert bool(jnp.any(scatter & (lay.v_off // bn + 1 > last)))
    # the kernel's two sorted blocks lie inside the array (the interpreter
    # would clamp an out-of-range block where the chip reads past the end)
    lo, hi = kmod._sorted_blocks(lay.v_off, bn, last)
    assert bool(jnp.all((0 <= lo) & (lo <= hi) & (hi <= last)))
    shape = (n,) if k is None else (n, k)
    beta = jax.random.normal(jax.random.PRNGKey(k or 0), shape)
    got = bin_fused_matvec_op(index, beta, interpret=True, average=average)
    want = _padded_matvec(index, beta, average)
    assert got.shape == want.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and both agree with the split reference matvec
    ref = table_readout(index, table_loads(index, beta), average=average)
    np.testing.assert_allclose(got, ref, atol=2e-5)


def test_blocked_layout_schedules_O_n_tiles():
    """The visit schedule is O(n/bn + B/bt) per instance — linear in n when
    B = Θ(n) — not the (n/bn)·(B/bt) cross product of a dense grid."""
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
    cross_product = (n // bn) * n_tiles       # cross-product grid/instance
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
    beta, fused_ref, fused_pal, sidx, fidx, fidx_pal = \
        _setup(key, n, d, m, table_size)
    for op, idx in ((fused_ref, fidx), (fused_pal, fidx_pal)):
        hlo_split = jax.jit(lambda b: op.readout(idx, op.loads(idx, b))) \
            .lower(beta).compile().as_text()
        hlo_fused = jax.jit(lambda b: op.matvec(idx, b)) \
            .lower(beta).compile().as_text()
        # the kernels carry the table with a unit row axis, (m, 1, B)
        shapes = ((m, table_size), (m, 1, table_size))
        assert any(materializes_shape(hlo_split, s) for s in shapes)
        assert not any(materializes_shape(hlo_fused, s) for s in shapes)


def test_wlsh_krr_fit_bitwise_stable_across_fused_toggle():
    """The fit's fused solve on the reference backend produces
    bitwise-identical (beta, tables) to a split solve — the same LSH, PCG
    over ``table_matvec`` on a layout-less index, tables by
    ``table_loads``: the stable slot sort keeps every bucket's
    contributions in the same addition order."""
    key = jax.random.PRNGKey(0)
    n, d, m, lam, maxiter = 300, 3, 16, 0.5, 60
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    m_fused = wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec, m=m,
                           lam=lam, maxiter=maxiter, backend="reference")
    lsh = sample_lsh_params(jax.random.fold_in(key, 2), m, d, spec.pdf,
                            spec.lengthscale)
    op = make_operator(lsh, spec.bucket, m_fused.table_size,
                       backend="reference")
    sidx = build_table_index(op.featurize(x), m_fused.table_size)
    res = pcg_solve(lambda v: table_matvec(sidx, v), y, lam, tol=1e-5,
                    maxiter=maxiter)
    np.testing.assert_array_equal(np.asarray(m_fused.beta),
                                  np.asarray(res.x))
    np.testing.assert_array_equal(np.asarray(m_fused.tables),
                                  np.asarray(table_loads(sidx, res.x)))
    assert int(m_fused.cg_iters) == int(res.col_iters[0])


def test_distributed_fused_local_matvec_single_data_shard():
    """Data axes of size 1: make_krr_step takes the fused local-matvec branch
    (no table psum needed) and must be bitwise-equal to a split step on a
    layout-less index (loads -> data psum -> instance-sum readout -> model
    psum / m) — same guarantee as the single-host fused solve."""
    import functools

    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.distributed import (KRRStepConfig, _shard_operator,
                                        cg_iterations, make_krr_step)
    from repro.core.lsh import LSHParams
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    n, d, m, table_size = 192, 3, 4, 512
    key = jax.random.PRNGKey(6)
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    lsh = sample_lsh_params(jax.random.fold_in(key, 2), m, d,
                            GammaPDF(2.0, 1.0))
    f = get_bucket_fn("rect")
    cfg = KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=15,
                        data_axes=("pod", "data"), model_axis="model",
                        backend="reference")
    data = P(cfg.data_axes)
    lsh_spec = LSHParams(*(P("model", None),) * 4)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(cfg.data_axes, None), data, lsh_spec),
                       out_specs=(data, P(), P("model", None)))
    def split_step(x_local, y_local, lsh_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        idx = op.build_index(op.featurize(x_local), blocked=False)

        def mv(v):
            tables = jax.lax.psum(op.loads(idx, v), cfg.data_axes)
            out = op.readout(idx, tables, average=False)
            return jax.lax.psum(out, cfg.model_axis) / cfg.m
        beta, resnorm = cg_iterations(mv, y_local, cfg)
        return beta, resnorm, jax.lax.psum(op.loads(idx, beta),
                                           cfg.data_axes)

    b_f, r_f, t_f = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    b_s, r_s, t_s = jax.jit(split_step)(x, y, lsh)
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
