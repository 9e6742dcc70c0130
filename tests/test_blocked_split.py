"""Blocked distributed matvec: visit-list split kernels + slot-sort routing.

Pins the blocked split scatter/gather against the reference split path
(odd n, m=1, non-dividing tiles, k=8 multi-RHS) — bitwise for the gather,
ulp-level for the scatter (the one-hot
dot reduces a block's same-slot contributions in tree order where the
sequential scatter-add chains them; same operands, different association) —
explicit zeroing of table tiles no point hashes into, the per-pass
O(n/bn + B/bt) visit schedules, and the hash-join routing build containing
NO sort (it rides the slot-blocked layout's one stable argsort).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GammaPDF, get_bucket_fn, make_operator,
                        sample_lsh_params)
from repro.core.distributed import _routing_maps
from repro.core.wlsh import (BLOCKED_N, BLOCKED_T, TableIndex,
                             build_blocked_layout, build_table_index,
                             table_loads, table_matvec, table_readout)
from repro.hlo_analysis import count_ops
from repro.kernels.binning import (bin_loads_blocked_op,
                                   bin_readout_blocked_op)


def _setup(key, n, d, m, table_size, block_n=64, block_t=512):
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="reference")
    feats = op.featurize(x)
    idx = build_table_index(feats, table_size)
    lay = build_blocked_layout(idx.slot, idx.coeff, table_size,
                               block_n=block_n, block_t=block_t,
                               parts="pallas")
    return beta, idx, idx._replace(blocked=lay)


# odd n, n < block_n, m=1, table sizes from one tile up, non-dividing tiles
@pytest.mark.parametrize("n,d,m,table_size,bn,bt",
                         [(97, 3, 2, 512, 64, 512),
                          (300, 5, 4, 1024, 128, 384),
                          (128, 2, 1, 256, 64, 512),
                          (257, 3, 3, 2048, 64, 512)])
def test_blocked_split_matches_unblocked_split(n, d, m, table_size, bn, bt):
    key = jax.random.PRNGKey(n + d + m)
    beta, idx, bidx = _setup(key, n, d, m, table_size, bn, bt)
    want = table_loads(idx, beta)                    # reference split scatter
    got = bin_loads_blocked_op(bidx, beta, interpret=True)
    assert got.shape == want.shape                   # psum contract unchanged
    np.testing.assert_allclose(got, want, atol=1e-5)
    # gather is pure selection — bitwise against the reference split
    out_want = table_readout(idx, want)
    out_got = bin_readout_blocked_op(bidx, jnp.asarray(want), interpret=True)
    np.testing.assert_array_equal(np.asarray(out_got), np.asarray(out_want))
    # sum mode (the distributed model-axis contribution)
    np.testing.assert_array_equal(
        np.asarray(bin_readout_blocked_op(bidx, jnp.asarray(want),
                                          average=False, interpret=True)),
        np.asarray(table_readout(idx, want, average=False)))


def test_blocked_split_multi_rhs_k8():
    """A (n, 8) RHS block rides the same visit schedule: (m, B, k) tables
    bitwise-shaped like the per-column split path, values within an ulp."""
    n, d, m, table_size, k = 300, 4, 3, 1024, 8
    key = jax.random.PRNGKey(7)
    _, idx, bidx = _setup(key, n, d, m, table_size)
    bk = jax.random.normal(jax.random.fold_in(key, 3), (n, k))
    want = table_loads(idx, bk)                      # (m, B, k)
    got = bin_loads_blocked_op(bidx, bk, interpret=True)
    assert got.shape == want.shape == (m, table_size, k)
    np.testing.assert_allclose(got, want, atol=1e-5)
    out_want = table_readout(idx, want)
    out_got = bin_readout_blocked_op(bidx, jnp.asarray(want), interpret=True)
    assert out_got.shape == (n, k)
    np.testing.assert_array_equal(np.asarray(out_got), np.asarray(out_want))


def test_blocked_split_matvec_through_operator():
    """A pallas operator's loads and readout take the visit-list kernels
    whenever the index carries the layout — same matvec as the reference
    split."""
    n, d, m, table_size = 300, 3, 4, 1024
    key = jax.random.PRNGKey(11)
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="pallas")
    feats = op.featurize(x)
    bidx = op.build_index(feats, blocked=True)       # the one kernel geometry
    assert bidx.blocked is not None
    assert bidx.blocked.block_n == BLOCKED_N
    assert bidx.blocked.block_t == BLOCKED_T
    ref = make_operator(lsh, get_bucket_fn("rect"), table_size,
                        backend="reference")
    ridx = ref.build_index(feats, blocked=False)
    want = ref.matvec(ridx, beta)
    tables = op.loads(bidx, beta)
    np.testing.assert_allclose(op.readout(bidx, tables), want, atol=1e-5)
    np.testing.assert_allclose(
        op.readout(bidx, tables, average=False),
        ref.matvec(ridx, beta, average=False), atol=1e-4)


def test_blocked_scatter_zeroes_unvisited_tiles():
    """A table tile no point hashes into must come back EXACTLY zero: the
    scatter schedule gives it one visit against the all-padding block, which
    zeroes its HBM tile and adds nothing."""
    m, n, table_size, bt = 2, 64, 1024, 256          # 4 tiles of 256
    # every slot in tile 0 or tile 2 — tiles 1 and 3 are never hit
    key = jax.random.PRNGKey(3)
    raw = jax.random.randint(key, (m, n), 0, 256)
    slot = jnp.where(jnp.arange(n)[None, :] % 2 == 0, raw, raw + 512)
    coeff = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    idx = TableIndex(slot=slot.astype(jnp.int32), sign=jnp.sign(coeff),
                     weight=jnp.abs(coeff), coeff=coeff,
                     table_size=table_size)
    lay = build_blocked_layout(idx.slot, idx.coeff, table_size,
                               block_n=64, block_t=bt, parts="pallas")
    # the scatter schedule still covers every tile at least once
    for s in range(m):
        assert set(np.asarray(lay.vs_tile[s])) == set(range(4))
    bidx = idx._replace(blocked=lay)
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    tables = bin_loads_blocked_op(bidx, beta, interpret=True)
    assert bool(jnp.all(tables[:, 256:512] == 0.0))
    assert bool(jnp.all(tables[:, 768:] == 0.0))
    np.testing.assert_allclose(tables, table_loads(idx, beta), atol=1e-5)
    # full round trip through the gather stays exact
    np.testing.assert_allclose(
        bin_readout_blocked_op(bidx, tables, interpret=True),
        table_matvec(idx, beta), atol=1e-5)


def test_split_schedule_is_O_n_per_pass():
    """Each split pass is NB = n/bn + ceil(B/bt) visits per instance — not
    the (n/bn)·(B/bt) cross product — and the scatter schedule's tiles are
    ascending with every tile present (the zero-init contract)."""
    n, d, m, table_size = 4096, 4, 3, 16384
    bn, bt = 64, 512
    key = jax.random.PRNGKey(5)
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), table_size,
                       backend="reference")
    idx = op.build_index(op.featurize(x), blocked=False)
    lay = build_blocked_layout(idx.slot, idx.coeff, table_size,
                               block_n=bn, block_t=bt, parts="pallas")
    nb = n // bn + table_size // bt
    assert lay.vs_block.shape == (m, nb)
    assert lay.vs_tile.shape == (m, nb)
    assert lay.vg_tile.shape == (m, nb)
    assert nb < (n // bn) * (table_size // bt) / 8   # cross product
    vt = np.asarray(lay.vs_tile)
    assert (np.diff(vt, axis=1) >= 0).all()          # ascending, contiguous
    for s in range(m):
        assert set(vt[s]) == set(range(table_size // bt))


def test_routing_maps_contains_no_sort():
    """Acceptance criterion: the hash-join routing build rides the blocked
    layout's slot sort — its own lowering contains ZERO sort ops."""
    m, n, table_size, n_shards = 3, 200, 1024, 4
    key = jax.random.PRNGKey(9)
    slot = jax.random.randint(key, (m, n), 0, table_size).astype(jnp.int32)
    coeff = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    lay = build_blocked_layout(slot, coeff, table_size, parts="reference")
    fn = jax.jit(lambda s, la: _routing_maps(s, la, n_shards, table_size,
                                             2.0))
    hlo = fn.lower(slot, lay).compile().as_text()
    assert count_ops(hlo, "sort") == 0
    # ... and the layout build itself is exactly the one stable argsort
    lay_fn = jax.jit(lambda s, c: build_blocked_layout(s, c, table_size,
                                                       parts="reference"))
    hlo_lay = lay_fn.lower(slot, coeff).compile().as_text()
    assert count_ops(hlo_lay, "sort") == 1


def test_hashjoin_step_single_device_matches_psum_and_single_sort():
    """On a trivial mesh the hash-join step must agree with the psum step
    (dedup exact: cap is bounded by the owner's m·spp distinct cells), and
    its whole lowered program must contain exactly ONE sort — the layout's."""
    from repro.compat import make_mesh
    from repro.core.distributed import (KRRStepConfig, make_krr_step,
                                        make_krr_step_hashjoin)
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
    b_ref, _, _ = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    hj = jax.jit(make_krr_step_hashjoin(mesh, cfg, f,
                                        payload_dtype=jnp.float32))
    b_hj, _, _, _ = hj(x, y, lsh)
    np.testing.assert_allclose(np.asarray(b_hj), np.asarray(b_ref),
                               atol=1e-5)
    hlo = hj.lower(x, y, lsh).compile().as_text()
    assert count_ops(hlo, "sort") == 1


# ---------------------------------------------------------------------------
# hash-join route kernels (PR 6): pack/unpack vs the flat-XLA scatter/gather
# ---------------------------------------------------------------------------

def _route_setup(m=3, n=200, table_size=1024, n_shards=2, cap_factor=2.0,
                 seed=9):
    from repro.core.distributed import (_make_route_plan, _routing_maps)
    key = jax.random.PRNGKey(seed)
    slot = jax.random.randint(key, (m, n), 0, table_size).astype(jnp.int32)
    coeff = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    lay = build_blocked_layout(slot, coeff, table_size,
                               block_n=BLOCKED_N,
                               block_t=BLOCKED_T, parts="both")
    pt_cell, _, spp, cap, _, _ = _routing_maps(slot, lay, n_shards,
                                               table_size, cap_factor)
    nb = n_shards * cap
    plan = _make_route_plan(pt_cell, lay, nb)
    return lay, pt_cell, plan, nb, coeff


@pytest.mark.parametrize("k", [None, 1, 4])
def test_route_pack_kernel_matches_flat_scatter(k):
    """The Pallas route-pack kernel reproduces the flat scatter-add through
    pt_cell exactly (bucket segment-sum inside the one-hot accumulation;
    dropped points land on the sentinel and vanish)."""
    from repro.kernels.binning import route_pack_pallas
    lay, pt_cell, plan, nb, coeff = _route_setup()
    key = jax.random.PRNGKey(3)
    shape = (200,) if k is None else (200, k)
    beta = jax.random.normal(key, shape)
    tail = beta.shape[1:]
    contrib = (coeff[:, :, None] * beta[None] if k is not None
               else coeff * beta[None, :])
    want = jnp.zeros((nb + 1,) + tail).at[pt_cell.reshape(-1)].add(
        contrib.reshape((-1,) + tail))[:nb]
    sched = plan.sched
    pad = jnp.zeros((1,) + tail)
    beta_lay = jnp.concatenate([beta, pad])[lay.src]
    if k is not None:
        contrib_lay = lay.coeff_lay[:, None, :] * jnp.swapaxes(beta_lay, 1, 2)
    else:
        contrib_lay = lay.coeff_lay * beta_lay
    packed = route_pack_pallas(
        sched.p_inst, sched.p_block, sched.p_tile, sched.p_flag,
        plan.cell_lay, contrib_lay, num_cell_tiles=sched.num_cell_tiles,
        block_n=lay.block_n, block_t=sched.block_t, interpret=True)
    got = packed[:, :nb].T if k is not None else packed[0, :nb]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("k", [None, 4])
def test_route_unpack_kernel_matches_flat_gather(k):
    """The Pallas route-unpack kernel reproduces the flat gather + coeff
    product through pt_cell (sentinel cells read zero; every layout block is
    written, including blocks with no real cells)."""
    from repro.kernels.binning import route_unpack_pallas
    lay, pt_cell, plan, nb, coeff = _route_setup()
    key = jax.random.PRNGKey(4)
    m = coeff.shape[0]
    tail = () if k is None else (k,)
    back = jax.random.normal(key, (nb,) + tail)
    back_pad = jnp.concatenate([back, jnp.zeros((1,) + tail)])
    vals = back_pad[pt_cell]
    contrib = coeff[:, :, None] * vals if k is not None else coeff * vals
    want = jnp.sum(contrib, axis=0)
    sched = plan.sched
    cbbt = sched.num_cell_tiles * sched.block_t
    buf = jnp.pad(back, ((0, cbbt - nb),) + ((0, 0),) * len(tail))
    buf = buf.T if k is not None else buf[None]
    out_lay = route_unpack_pallas(
        sched.u_block, sched.u_tile, sched.u_flag, plan.cell_lay,
        lay.coeff_lay, buf, block_n=lay.block_n, block_t=sched.block_t,
        interpret=True)
    rows = jnp.arange(m)[:, None]
    if k is not None:
        got = jnp.swapaxes(out_lay, 1, 2)[rows, lay.inv_pos].sum(axis=0)
    else:
        got = out_lay[rows, lay.inv_pos].sum(axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_route_schedule_contains_no_sort():
    """The route-kernel schedule build (cells -> visit lists) is cumsum /
    searchsorted only — the single-sort-per-step pin survives the fused
    kernels."""
    from repro.core.distributed import _make_route_plan, _routing_maps
    m, n, table_size, n_shards = 3, 200, 1024, 4
    key = jax.random.PRNGKey(9)
    slot = jax.random.randint(key, (m, n), 0, table_size).astype(jnp.int32)
    coeff = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    lay = build_blocked_layout(slot, coeff, table_size,
                               block_n=BLOCKED_N,
                               block_t=BLOCKED_T, parts="both")

    def plan_fn(s):
        # lay closed over (its block geometry fields are static ints)
        pt_cell, _, _, cap, _, _ = _routing_maps(s, lay, n_shards,
                                                 table_size, 2.0)
        return _make_route_plan(pt_cell, lay, n_shards * cap)

    hlo = jax.jit(plan_fn).lower(slot).compile().as_text()
    assert count_ops(hlo, "sort") == 0


# ---------------------------------------------------------------------------
# visit schedules larger than one call's SMEM budget
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["fused", "fused_k4", "scatter", "gather",
                                    "pack", "unpack"])
def test_visit_kernels_split_over_smem_budget(monkeypatch, kernel):
    """A schedule over the SMEM budget runs as several calls — instance
    groups for the per-instance kernels, consecutive chunks (each resuming
    the tile the previous one left open) for the flat route pack — and must
    give exactly the one-call result."""
    from repro.kernels.binning import (bin_fused_matvec_pallas,
                                       bin_gather_blocked_pallas,
                                       bin_scatter_blocked_pallas,
                                       route_pack_pallas, route_unpack_pallas)
    from repro.kernels.binning import kernel as kmod
    lay, _, plan, nb, coeff = _route_setup(m=4)
    sched = plan.sched
    key = jax.random.PRNGKey(12)
    m, length = lay.slot_lay.shape
    beta = jax.random.normal(key, (m, length))
    beta4 = jax.random.normal(key, (m, 4, length))
    # beta in sorted order for the fused kernel: n = 200 -> 2 whole blocks
    sorted_len = -(-lay.order.shape[1] // lay.block_n) * lay.block_n
    beta_s = beta[:, :sorted_len]
    beta4_s = beta4[:, :, :sorted_len]
    calls = {
        "fused": (lambda: bin_fused_matvec_pallas(
            lay.v_block, lay.v_tile_phase, lay.v_off, lay.slot_lay,
            lay.coeff_lay, beta_s, block_n=lay.block_n, block_t=lay.block_t,
            interpret=True), 3 * lay.v_block.shape[1]),
        "fused_k4": (lambda: bin_fused_matvec_pallas(
            lay.v_block, lay.v_tile_phase, lay.v_off, lay.slot_lay,
            lay.coeff_lay, beta4_s, block_n=lay.block_n,
            block_t=lay.block_t, interpret=True), 3 * lay.v_block.shape[1]),
        "scatter": (lambda: bin_scatter_blocked_pallas(
            lay.vs_block, lay.vs_tile, lay.slot_lay, beta,
            num_tiles=lay.num_tiles, block_n=lay.block_n,
            block_t=lay.block_t, interpret=True), 2 * lay.vs_block.shape[1]),
        "gather": (lambda: bin_gather_blocked_pallas(
            lay.vg_tile, lay.slot_lay,
            jax.random.normal(key, (m, lay.num_tiles * lay.block_t)),
            block_n=lay.block_n, block_t=lay.block_t, interpret=True),
            lay.vg_tile.shape[1]),
        "pack": (lambda: route_pack_pallas(
            sched.p_inst, sched.p_block, sched.p_tile, sched.p_flag,
            plan.cell_lay, beta, num_cell_tiles=sched.num_cell_tiles,
            block_n=lay.block_n, block_t=sched.block_t, interpret=True),
            4 * 7),
        "unpack": (lambda: route_unpack_pallas(
            sched.u_block, sched.u_tile, sched.u_flag, plan.cell_lay,
            lay.coeff_lay, jax.random.normal(
                key, (1, sched.num_cell_tiles * sched.block_t)),
            block_n=lay.block_n, block_t=sched.block_t, interpret=True),
            3 * sched.u_block.shape[1]),
    }
    call, words = calls[kernel]
    whole = np.asarray(call())
    jax.clear_caches()
    # one instance per call, or 7-visit chunks of the flat pack schedule
    monkeypatch.setattr(kmod, "SMEM_SCHEDULE_BYTES", 4 * words)
    split = np.asarray(call())
    jax.clear_caches()
    np.testing.assert_array_equal(split, whole)
    if kernel != "pack":
        monkeypatch.setattr(kmod, "SMEM_SCHEDULE_BYTES", 4 * words - 4)
        with pytest.raises(ValueError, match="SMEM"):
            call()
        jax.clear_caches()
