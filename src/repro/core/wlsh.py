"""WLSH estimator (paper Def. 6) — kernel matvec data structures.

Two execution modes:

* **exact** — groups equal buckets by lexicographic sort of the two 32-bit keys
  and uses ``segment_sum`` for the bucket loads.  This is the paper's estimator
  verbatim (up to 2^-64 hash collisions) and is the validation / small-scale
  path.

* **table** (CountSketch) — scatters signed loads into a dense table of size B.
  Cross-bucket collisions are sign-randomized, so the estimator stays unbiased
  and the implied kernel matrix (S Phi)(S Phi)^T stays PSD.  The dense table is
  ``psum``-able across data shards, which is what makes the method run on a
  512-chip mesh (see core/distributed.py).

Both modes expose ``matvec`` computing (1/m) sum_s K̃^s beta in O(n·m).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .bucket_fns import BucketFn
from .lsh import Features, LSHParams, featurize, slots_from_features

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# exact mode: sort + segment-sum
# ---------------------------------------------------------------------------

class ExactIndex(NamedTuple):
    """Per-instance sorted bucket structure for a fixed point set."""

    perm: Array      # (m, n) int32 — sort order by (key1, key2)
    seg_id: Array    # (m, n) int32 — bucket id of sorted position (0..n-1)
    weight: Array    # (m, n) float32 — WLSH weights (unsorted order)


def build_exact_index(feats: Features) -> ExactIndex:
    def one(key1, key2):
        # lexsort: secondary key first.
        perm = jnp.lexsort((key2, key1))
        k1s, k2s = key1[perm], key2[perm]
        new_seg = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            ((k1s[1:] != k1s[:-1]) | (k2s[1:] != k2s[:-1])).astype(jnp.int32),
        ])
        seg_id = jnp.cumsum(new_seg)
        return perm.astype(jnp.int32), seg_id.astype(jnp.int32)

    perm, seg_id = jax.vmap(one)(feats.key1, feats.key2)
    return ExactIndex(perm=perm, seg_id=seg_id, weight=feats.weight)


# Names of the table matvec's parts in JAX's name stack, shared by the
# reference primitives below and the kernel-backed ops (kernels/binning):
# the scatter/gather itself, and the moves around it (padding, gathers into
# and out of a slot order, the coefficient product and instance mean).
# Inside a traced program (the PCG loop's body) they reach the HLO metadata,
# so a device trace tells layout time from kernel time.
KERNEL_SCOPE = "wlsh.matvec.kernel"
LAYOUT_SCOPE = "wlsh.matvec.layout"


def _colwise(coeff: Array, v: Array) -> Array:
    """coeff ⊙ v for v of shape (n,) or (n, k) (coeff broadcast over RHS
    columns).  The single place the multi-RHS axis convention lives."""
    return coeff * v if v.ndim == 1 else coeff[:, None] * v


def exact_matvec(index: ExactIndex, beta: Array) -> Array:
    """(1/m) sum_s K̃^s beta — O(m n) (after the one-off O(m n log n) sort).
    ``beta`` is (n,) or (n, k); k right-hand sides share the sort."""
    n = beta.shape[0]

    def one(perm, seg_id, weight):
        contrib = _colwise(weight, beta)[perm]
        loads = jax.ops.segment_sum(contrib, seg_id, num_segments=n)
        out_sorted = _colwise(weight[perm], loads[seg_id])
        return jnp.zeros_like(contrib).at[perm].set(out_sorted)

    outs = jax.vmap(one)(index.perm, index.seg_id, index.weight)
    return jnp.mean(outs, axis=0)


def exact_kernel_matrix(feats: Features) -> Array:
    """Explicit K̃ = (1/m) sum_s K̃^s — O(m n^2); tests/small-n only."""
    eq = (feats.key1[:, :, None] == feats.key1[:, None, :]) & \
         (feats.key2[:, :, None] == feats.key2[:, None, :])
    ww = feats.weight[:, :, None] * feats.weight[:, None, :]
    return jnp.mean(eq * ww, axis=0)


# ---------------------------------------------------------------------------
# table (CountSketch) mode
# ---------------------------------------------------------------------------

# Layout geometry of the visit-list kernels (fused and split alike): one
# point block of the sorted layout and one table tile.  bn = 128 is the
# narrowest lane-dense block a TPU kernel accepts and keeps tile-capacity
# padding small (a nonempty tile wastes at most bn-1 layout slots).
BLOCKED_N = 128
BLOCKED_T = 512


class BlockedLayout(NamedTuple):
    """Slot-blocked point layout for a fixed (point set, table geometry).

    Points of every instance are stably sorted by CountSketch slot and packed
    into ``block_n``-point blocks such that each block addresses exactly ONE
    ``block_t``-slot table tile.  A Pallas grid over the resulting visit list
    therefore only touches (point-block, table-tile) pairs that actually
    collide — O(n/bn + B/bt) tiles per instance instead of the (n/bn)·(B/bt)
    cross product.  ``L = NB·bn`` with ``NB = n//bn + ceil(B/bt)`` is the
    static layout length (tile-capacity rounding); padding slots carry
    ``coeff = 0`` so they can never perturb loads or readouts.

    Visit v of instance s processes layout block ``v_block[s, v]`` against
    tile ``v_tile_phase[s, v] // 2``; its parity is 0 for the scatter pass
    and 1 for the gather pass.  Per tile, all scatter visits precede all
    gather visits, and tiles appear in ascending order, so one VMEM-resident
    tile serves both passes.  Visits past ``n_visits[s]`` re-gather the last
    real block (idempotent no-ops that keep the grid static).

    The fused kernel reads beta in each instance's sorted order
    (``beta[order[s]]``, n values) rather than along the padded layout (L
    values): layout block q of tile t is the run of sorted points that
    starts q·bn after the tile's first, and ``v_off[s, v]`` is that offset
    for a scatter visit.  Its lanes past the tile's points read the next
    points' beta, which their coeff 0 cancels.  Gather and padding visits
    read no beta and repeat the offset of the visit before them, so the
    kernel's pipeline fetches no beta block for them.

    The **split** kernels (distributed psum path — the (m, B) table must
    round-trip through HBM as the scatter→psum→gather barrier) ride the same
    sort through two per-pass schedules of NB visits each instead of the
    (n/bn)·(B/bt) cross product:

    * ``vs_block``/``vs_tile`` drive ``bin_scatter_blocked_pallas``: every
      table tile is visited at least once (tiles ascending, each tile's
      visits contiguous, so the revisited HBM output tile is zeroed exactly
      once on its first visit) — tiles no point hashes into get one visit
      pairing them with the all-padding layout block, which zeroes them
      explicitly and adds nothing.
    * ``vg_tile[s, j]`` is the one tile layout block j addresses, driving
      ``bin_gather_blocked_pallas`` (every block written exactly once;
      padding blocks carry slot 0 and read tile 0 — positions never mapped
      back through ``inv_pos``).

    Each backend consumes a disjoint array group, so ``build_blocked_layout``
    gates construction on ``parts`` ('reference' | 'pallas' | 'both'); the
    unbuilt group's fields are None.
    """

    # reference (sorted segment-sum) group:
    perm: Array          # (m, n) int32 — stable argsort of slot per instance
    seg_id: Array        # (m, n) int32 — dense rank of each sorted slot
    seg_pt: Array        # (m, n) int32 — segment of original point i
    coeff_sorted: Array  # (m, n) float32 — coeff in sorted order
    # pallas (fused kernel) group:
    order: Array      # (m, n) int32 — stable argsort of slot per instance
    inv_pos: Array    # (m, n) int32 — layout position of original point i
    src: Array        # (m, L) int32 — original point per layout slot (n = pad)
    slot_lay: Array   # (m, L) int32 — CountSketch slot per layout position
    coeff_lay: Array  # (m, L) float32 — weight·sign per position (0 = pad)
    v_block: Array    # (m, V) int32 — visit -> layout block
    v_tile_phase: Array  # (m, V) int32 — visit -> 2·table tile + phase
                         #   (phase 0 scatter, 1 gather)
    v_off: Array      # (m, V) int32 — visit -> offset into ``order`` of the
                      #   beta block it reads (scatter: its layout block's
                      #   first sorted point; else the previous visit's)
    # pallas split-kernel (per-pass) schedules, NB = n//bn + ceil(B/bt):
    vs_block: Array   # (m, NB) int32 — scatter visit -> layout block
    vs_tile: Array    # (m, NB) int32 — scatter visit -> table tile (covers
                      #   every tile at least once; ascending, contiguous)
    vg_tile: Array    # (m, NB) int32 — layout block -> its table tile
    # always present:
    n_visits: Array   # (m,) int32 — real visits (<= V = 2·(n//bn + B/bt))
    block_n: int
    block_t: int
    num_tiles: int


class TableIndex(NamedTuple):
    slot: Array    # (m, n) int32 in [0, B)
    sign: Array    # (m, n) float32
    weight: Array  # (m, n) float32
    coeff: Array   # (m, n) float32 — weight·sign, hoisted out of CG iterations
    table_size: int
    blocked: BlockedLayout | None = None


def build_table_index(feats: Features, table_size: int) -> TableIndex:
    return TableIndex(slot=slots_from_features(feats, table_size),
                      sign=feats.sign, weight=feats.weight,
                      coeff=feats.weight * feats.sign, table_size=table_size)


def build_blocked_layout(slot: Array, coeff: Array, table_size: int, *,
                         block_n: int = BLOCKED_N,
                         block_t: int = BLOCKED_T,
                         parts: str = "both") -> BlockedLayout:
    """One-off O(mn log n) construction of the slot-blocked layout.

    Pure jnp (jit/shard_map safe).  ``table_size`` need not divide
    ``block_t`` — the tile grid covers ceil(table_size / block_t) tiles and
    trailing tiles are simply never populated.  ``parts`` selects which
    backend's array group to materialize ('reference' | 'pallas' | 'both'):
    the groups are disjoint and sized O(mn)–O(mL), so a reference solve
    should not carry the kernel's visit lists through CG (and vice versa).
    """
    if parts not in ("reference", "pallas", "both"):
        raise ValueError(f"unknown parts {parts!r}")
    want_ref = parts in ("reference", "both")
    want_pal = parts in ("pallas", "both")
    m, n = slot.shape
    bn, bt = int(block_n), int(block_t)
    num_tiles = -(-int(table_size) // bt)
    # Static block budget: sum_t ceil(c_t/bn) <= n//bn + num_tiles because
    # sum floor(c_t/bn) <= n//bn and at most one partial block per tile.
    nb = n // bn + num_tiles
    layout_len = nb * bn
    n_vis = 2 * nb

    def one(slot_row, coeff_row):
        order = jnp.argsort(slot_row).astype(jnp.int32)        # stable sort
        ss = slot_row[order]
        tile = ss // bt                                        # (n,) in [0, T)

        ref_group = None
        if want_ref:
            new_seg = jnp.concatenate([
                jnp.zeros((1,), jnp.int32),
                (ss[1:] != ss[:-1]).astype(jnp.int32)])
            seg_id = jnp.cumsum(new_seg).astype(jnp.int32)
            seg_pt = jnp.zeros((n,), jnp.int32).at[order].set(seg_id)
            ref_group = (order, seg_id, seg_pt, coeff_row[order])

        counts = jnp.zeros((num_tiles,), jnp.int32).at[tile].add(1)
        kblocks = -(-counts // bn)                             # blocks per tile
        blk_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                     jnp.cumsum(kblocks).astype(jnp.int32)])
        total_blocks = blk_start[-1]

        pal_group = None
        if want_pal:
            # layout position of sorted point r: tile start + within-tile rank
            first_idx = jnp.searchsorted(tile, jnp.arange(num_tiles,
                                                          dtype=tile.dtype))
            rank = jnp.arange(n, dtype=jnp.int32) - \
                first_idx[tile].astype(jnp.int32)
            pos = blk_start[tile] * bn + rank
            src = jnp.full((layout_len,), n, jnp.int32).at[pos].set(order)
            slot_lay = jnp.zeros((layout_len,), jnp.int32).at[pos].set(ss)
            coeff_lay = jnp.zeros((layout_len,), jnp.float32).at[pos].set(
                coeff_row[order])
            inv_pos = jnp.zeros((n,), jnp.int32).at[order].set(pos)

            # visit list: per tile t, scatter its blocks then gather them;
            # tile t's visits fill [2·blk_start[t], 2·blk_start[t+1])
            barange = jnp.arange(nb, dtype=jnp.int32)
            block_tile = jnp.minimum(
                jnp.searchsorted(blk_start[1:], barange, side="right"),
                num_tiles - 1).astype(jnp.int32)
            q = barange - blk_start[block_tile]
            v_s = 2 * blk_start[block_tile] + q
            v_g = v_s + kblocks[block_tile]
            real = barange < total_blocks
            vs_idx = jnp.where(real, v_s, n_vis)               # OOB -> dropped
            vg_idx = jnp.where(real, v_g, n_vis)
            v_block = jnp.zeros((n_vis,), jnp.int32) \
                .at[vs_idx].set(barange, mode="drop") \
                .at[vg_idx].set(barange, mode="drop")
            v_tile = jnp.zeros((n_vis,), jnp.int32) \
                .at[vs_idx].set(block_tile, mode="drop") \
                .at[vg_idx].set(block_tile, mode="drop")
            v_phase = jnp.zeros((n_vis,), jnp.int32) \
                .at[vg_idx].set(1, mode="drop")
            # padding visits: re-gather the last real block against the
            # (still loaded) last tile — rewrites the same values, never
            # zeroes the tile
            last_b = jnp.maximum(total_blocks - 1, 0)
            pad = jnp.arange(n_vis, dtype=jnp.int32) >= 2 * total_blocks
            v_block = jnp.where(pad, last_b, v_block)
            v_tile = jnp.where(pad, block_tile[last_b], v_tile)
            v_phase = jnp.where(pad, 1, v_phase)
            # beta offsets in sorted order: block b of tile t starts at
            # sorted point first_idx[t] + (b - blk_start[t])·bn.  Gather
            # and padding visits take the offset of their tile's last
            # block, the one its last scatter visit read
            off_block = jnp.where(v_phase == 1, blk_start[v_tile + 1] - 1,
                                  v_block)
            v_off = first_idx[v_tile].astype(jnp.int32) \
                + (off_block - blk_start[v_tile]) * bn

            # split-kernel per-pass schedules (NB visits each).  Scatter:
            # tile t owns visits [vstart[t], vstart[t+1]) with at least one
            # visit per tile — empty tiles pair with layout block nb-1,
            # which is all padding (coeff 0) whenever an empty tile exists
            # (total_blocks <= n//bn + #nonempty <= nb-1), so the visit
            # zeroes the tile's HBM output and adds nothing.
            ksched = jnp.maximum(kblocks, 1)
            vstart = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                      jnp.cumsum(ksched).astype(jnp.int32)])
            total_sched = vstart[-1]
            vj = jnp.arange(nb, dtype=jnp.int32)
            s_tile = jnp.minimum(
                jnp.searchsorted(vstart[1:], vj, side="right"),
                num_tiles - 1).astype(jnp.int32)
            q_s = vj - vstart[s_tile]
            s_block = jnp.where(counts[s_tile] > 0,
                                blk_start[s_tile] + q_s, nb - 1)
            # trailing padding visits revisit the last tile (no re-zeroing:
            # same tile as the previous visit) with the all-padding block
            pad_s = vj >= total_sched
            vs_tile = jnp.where(pad_s, num_tiles - 1, s_tile)
            vs_block = jnp.where(pad_s, nb - 1, s_block)
            # gather: block j reads its own tile exactly once; padding
            # blocks (slot_lay 0) read tile 0
            vg_tile = jnp.where(vj < total_blocks, block_tile, 0)
            pal_group = (order, inv_pos, src, slot_lay, coeff_lay,
                         v_block, 2 * v_tile + v_phase, v_off,
                         vs_block, vs_tile, vg_tile)
        return ref_group, pal_group, 2 * total_blocks

    ref_group, pal_group, n_visits = jax.vmap(one)(slot, coeff)
    perm, seg_id, seg_pt, coeff_sorted = ref_group or (None,) * 4
    (order, inv_pos, src, slot_lay, coeff_lay, v_block, v_tile_phase,
     v_off, vs_block, vs_tile, vg_tile) = pal_group or (None,) * 11
    return BlockedLayout(perm=perm, seg_id=seg_id, seg_pt=seg_pt,
                         coeff_sorted=coeff_sorted, order=order,
                         inv_pos=inv_pos, src=src, slot_lay=slot_lay,
                         coeff_lay=coeff_lay, v_block=v_block,
                         v_tile_phase=v_tile_phase, v_off=v_off,
                         vs_block=vs_block, vs_tile=vs_tile, vg_tile=vg_tile,
                         n_visits=n_visits.astype(jnp.int32),
                         block_n=bn, block_t=bt, num_tiles=num_tiles)


class RouteSchedule(NamedTuple):
    """Visit schedules for the hash-join route kernels (kernels/binning).

    Built by ``build_route_schedule`` from a per-instance monotone "cell"
    array laid out along the slot-blocked layout — for the hash join the
    cell is a point's destination slot in the flat all_to_all wire buffer.

    * Pack (contributions -> shared wire buffer): the output buffer is
      shared by every instance, so the schedule is FLAT and segmented by
      destination-cell tile — ``p_inst/p_block/p_tile/p_flag`` (V,) visits
      with each tile's segment contiguous, opened by a mandatory zero visit
      (flag 1), followed by every (instance, layout block) that reaches the
      tile (flag 0), with trailing no-ops (flag 2) re-targeting the last
      tile.  Consecutive same-tile visits keep the HBM output tile resident
      (the standard Pallas revisiting contract).
    * Unpack (wire buffer -> per-instance layout): per-instance lists
      ``u_block/u_tile/u_flag`` (m, VB) — every layout block visited at
      least once (blocks with no real cells gather zero against tile 0, so
      the output block is still written), blocks in order, one visit per
      cell tile a block spans, padding flagged 2.

    V = T + m·VB and VB = L/bn + T static (T = num_cell_tiles): per-instance
    cell ranges ascend block to block, so a block spans at most one tile
    boundary more than its predecessor — the same O(n/bn + B/bt) counting
    as the split visit lists.
    """

    p_inst: Array     # (V,) int32 — flat pack schedule: instance,
    p_block: Array    #   layout block,
    p_tile: Array     #   destination cell tile,
    p_flag: Array     #   0 = accumulate, 1 = zero the tile, 2 = no-op
    u_block: Array    # (m, VB) int32 — per-instance unpack schedule
    u_tile: Array
    u_flag: Array     #   0 = compute, 2 = no-op padding
    num_cell_tiles: int
    block_t: int      # cell tile width


def build_route_schedule(cell_lay: Array, *, num_cell_tiles: int,
                         block_n: int, block_t: int) -> RouteSchedule:
    """Pure-jnp (NO sort) construction of both route-kernel schedules.

    ``cell_lay`` (m, L) int32: destination cell per slot-blocked layout
    position, with real cells NON-DECREASING along each instance's layout
    (guaranteed when cells follow the layout's slot sort — the hash-join
    routing's owner·cap + rank cells do) and the out-of-range sentinel
    ``num_cell_tiles·block_t`` on dropped/padding positions (sentinels may
    be interspersed anywhere; they produce all-zero one-hot rows in the
    kernels and are excluded from the tile-range bookkeeping here).
    """
    m, layout_len = cell_lay.shape
    bn, bt = int(block_n), int(block_t)
    lb = layout_len // bn                       # layout blocks per instance
    cb = int(num_cell_tiles)
    sentinel = cb * bt
    cells = cell_lay.reshape(m, lb, bn)
    real = cells < sentinel
    any_real = jnp.any(real, axis=2)                          # (m, LB)
    lo = jnp.min(jnp.where(real, cells, sentinel), axis=2) // bt
    hi = jnp.max(jnp.where(real, cells, -1), axis=2) // bt    # -1 if empty
    c = jnp.where(any_real, hi - lo + 1, 0).astype(jnp.int32)  # tiles/block
    lo = jnp.where(any_real, lo, 0).astype(jnp.int32)
    vb = lb + cb                                # static visits per instance
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]

    def enumerate_visits(c_row, lo_row):
        """(block, tile, valid) of each visit: block b gets c_row[b]
        consecutive visits covering tiles [lo[b], lo[b] + c[b])."""
        start = jnp.cumsum(c_row) - c_row                     # exclusive
        total = start[-1] + c_row[-1]
        v = jnp.arange(vb, dtype=jnp.int32)
        b = jnp.clip(jnp.searchsorted(start, v, side="right") - 1,
                     0, lb - 1).astype(jnp.int32)
        t = (lo_row[b] + v - start[b]).astype(jnp.int32)
        return b, t, v < total

    # -- pack: flat schedule segmented by destination tile ------------------
    pb, pt, pvalid = jax.vmap(enumerate_visits)(c, lo)
    pt = jnp.where(pvalid, pt, cb - 1)          # pads sort after real tiles
    # rank of a visit among its instance's visits to the same tile: visit
    # tiles are non-decreasing per instance, so first occurrences come from
    # searchsorted against the row itself
    first = jax.vmap(lambda t_row: jnp.searchsorted(t_row, t_row,
                                                    side="left"))(pt)
    prank = jnp.arange(vb, dtype=jnp.int32)[None, :] - first.astype(jnp.int32)
    cnt = jnp.zeros((m, cb), jnp.int32).at[rows, pt].add(
        pvalid.astype(jnp.int32))
    tot = jnp.sum(cnt, axis=0)                                # (T,)
    seg_size = 1 + tot                          # zero slot + real visits
    seg_start = jnp.cumsum(seg_size) - seg_size
    inst_off = jnp.cumsum(cnt, axis=0) - cnt                  # (m, T)
    v_cap = cb + m * vb
    fp = jnp.where(pvalid,
                   seg_start[pt] + 1 + inst_off[rows, pt] + prank, v_cap)
    flat = fp.reshape(-1)
    p_inst = jnp.zeros((v_cap,), jnp.int32).at[flat].set(
        jnp.broadcast_to(rows, (m, vb)).reshape(-1), mode="drop")
    p_block = jnp.zeros((v_cap,), jnp.int32).at[flat].set(
        pb.reshape(-1), mode="drop")
    # defaults place the trailing no-ops on the last tile (idempotent)
    p_tile = jnp.full((v_cap,), cb - 1, jnp.int32).at[flat].set(
        pt.reshape(-1), mode="drop")
    p_flag = jnp.full((v_cap,), 2, jnp.int32).at[flat].set(0, mode="drop")
    p_tile = p_tile.at[seg_start].set(jnp.arange(cb, dtype=jnp.int32))
    p_flag = p_flag.at[seg_start].set(1)

    # -- unpack: per-instance, every block visited at least once ------------
    cu = jnp.maximum(c, 1)
    ub, ut, uvalid = jax.vmap(enumerate_visits)(cu, lo)
    last_t = (lo[:, -1] + cu[:, -1] - 1).astype(jnp.int32)
    ub = jnp.where(uvalid, ub, lb - 1).astype(jnp.int32)
    ut = jnp.where(uvalid, ut, last_t[:, None]).astype(jnp.int32)
    u_flag = jnp.where(uvalid, 0, 2).astype(jnp.int32)
    return RouteSchedule(p_inst=p_inst, p_block=p_block, p_tile=p_tile,
                         p_flag=p_flag, u_block=ub, u_tile=ut, u_flag=u_flag,
                         num_cell_tiles=cb, block_t=bt)


def table_loads(index: TableIndex, beta: Array) -> Array:
    """Bucket-load tables for all m instances: (m, B) for beta (n,), or
    (m, B, k) for a (n, k) RHS block (one scatter, k stacked columns)."""
    with jax.named_scope(LAYOUT_SCOPE):
        contrib = jax.vmap(_colwise, in_axes=(0, None))(index.coeff, beta)
    m = index.slot.shape[0]
    with jax.named_scope(KERNEL_SCOPE):
        tables = jnp.zeros((m, index.table_size) + beta.shape[1:],
                           contrib.dtype)
        rows = jnp.arange(m, dtype=jnp.int32)[:, None]
        return tables.at[rows, index.slot].add(contrib)


def table_readout(index: TableIndex, tables: Array, *,
                  average: bool = True) -> Array:
    """Per-point readout of the (possibly psum-merged) tables: (1/m) sum_s
    when ``average``, else the plain instance sum (distributed shards sum
    locally and divide by the global m after their model-axis psum).
    ``tables`` is (m, B) -> (n,) out, or (m, B, k) -> (n, k)."""
    with jax.named_scope(KERNEL_SCOPE):
        rows = jnp.arange(index.slot.shape[0], dtype=jnp.int32)[:, None]
        loads = tables[rows, index.slot]
    with jax.named_scope(LAYOUT_SCOPE):
        vals = jax.vmap(_colwise)(index.coeff, loads)
        return jnp.mean(vals, axis=0) if average else jnp.sum(vals, axis=0)


def table_matvec(index: TableIndex, beta: Array) -> Array:
    return table_readout(index, table_loads(index, beta))


def table_matvec_fused(index: TableIndex, beta: Array, *,
                       average: bool = True) -> Array:
    """Fused table matvec via sorted segment-sum — the reference fast path.

    Reuses the blocked layout's permutation: bucket loads are segment sums
    over the slot-sorted contributions (num_segments = n, not B), so the
    (m, B) table is never materialized and the work is O(nm) independent of
    the table size.  Per iteration this is one permuted gather, one segment
    sum and one gather back through the precomputed per-point segment ids —
    every permutation-derived array (``coeff_sorted``, ``seg_pt``) is hoisted
    into the layout.  The stable sort keeps every slot's contributions in
    original point order, which makes this bitwise-identical to
    ``table_readout(table_loads(beta))`` (both lower to sequential
    scatter-adds over the same per-slot operand order).

    ``beta`` is (n,) or (n, k): a RHS block rides the same permutation and
    segment ids — one segment-sum over (n, k) rows instead of k solves'
    worth of gathers, which is what amortizes multi-RHS CG.
    """
    lay = index.blocked
    if lay is None or lay.perm is None:
        raise ValueError("fused matvec needs a slot-blocked index with the "
                         "reference group; build it with build_blocked_layout"
                         "(parts='reference'|'both') / build_index(blocked=True)")
    n = beta.shape[0]

    def one(perm, seg_id, coeff_sorted, seg_pt, coeff):
        with jax.named_scope(LAYOUT_SCOPE):
            contrib = _colwise(coeff_sorted, beta[perm])
        with jax.named_scope(KERNEL_SCOPE):
            loads = jax.ops.segment_sum(contrib, seg_id, num_segments=n)
        with jax.named_scope(LAYOUT_SCOPE):
            return _colwise(coeff, loads[seg_pt])

    outs = jax.vmap(one)(lay.perm, lay.seg_id, lay.coeff_sorted, lay.seg_pt,
                         index.coeff)
    with jax.named_scope(LAYOUT_SCOPE):
        return jnp.mean(outs, axis=0) if average else jnp.sum(outs, axis=0)


def table_kernel_matrix(index: TableIndex) -> Array:
    """Explicit CountSketch kernel matrix (tests only): PSD by construction."""
    eq = index.slot[:, :, None] == index.slot[:, None, :]
    cc = index.coeff[:, :, None] * index.coeff[:, None, :]
    return jnp.mean(eq * cc, axis=0)


# ---------------------------------------------------------------------------
# high-level estimator façade
# ---------------------------------------------------------------------------

class WLSHEstimator(NamedTuple):
    """m independent WLSH instances bound to a bucket fn; the public API."""

    params: LSHParams
    bucket_name: str
    mode: str            # 'exact' | 'table'
    table_size: int

    def featurize(self, f: BucketFn, x: Array) -> Features:
        return featurize(self.params, f, x)


def make_matvec(feats: Features, mode: str = "exact", table_size: int = 0):
    """Returns (matvec_fn, index). matvec_fn is jit-compatible and closes over
    the prebuilt index (the paper's O(dn)-preprocessing / O(n)-matvec split)."""
    if mode == "exact":
        idx = build_exact_index(feats)
        return functools.partial(exact_matvec, idx), idx
    elif mode == "table":
        if table_size <= 0:
            raise ValueError("table mode needs table_size > 0")
        idx = build_table_index(feats, table_size)
        return functools.partial(table_matvec, idx), idx
    raise ValueError(f"unknown mode {mode!r}")
