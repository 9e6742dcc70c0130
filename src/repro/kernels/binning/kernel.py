"""Pallas TPU kernels: CountSketch bucket scatter/gather as one-hot reductions.

TPUs have no scatter atomics; the paper's bucket-load accumulation
(B_j += beta_i * weight_i) is re-expressed against a one-hot mask of one
point block against one table tile,

    hit (BT, BN) = iota_rows == slot_block - tile_lo
    table_tile (BT,) += sum over lanes of where(hit, contrib_block, 0)

and the readout gather (out_i = table[slot_i]) as the transposed reduction,
sum over sublanes of where(hit, table_tile, 0).  The masks never touch HBM —
they are built in VMEM per grid step from an iota compare.  Both reductions
run on the VPU in f32: a gather selects exactly one value per point, so it
is exact, and a scatter adds the same f32 terms as the reference scatter-add
in a different order.  (As one-hot matmuls these products have 1..k rows,
so the MXU would spend its time loading the mask as weights, and an f32
product there takes several bf16 passes.)  Every grid walks a visit list
in its trailing, sequential position, so a table tile visited by
consecutive steps accumulates in place (standard Pallas revisiting
pattern).

Two table-matvec kernel families, both driven by the slot-blocked layout:

* **fused** (``bin_fused_matvec_pallas``) — one ``pallas_call`` drives both
  products off a slot-blocked layout (``core.wlsh.BlockedLayout``): points
  are pre-sorted so each grid visit pairs one point block with the ONE table
  tile it collides with, the visit list is scalar-prefetched into SMEM so
  the BlockSpec index maps can follow the data-dependent schedule, and the
  table tile lives in a VMEM scratch for both the scatter and the gather
  pass — the (m, B) table never exists in HBM.  O(n/bn + B/bt) visits per
  instance: genuinely linear when B = Θ(n).  beta enters in each
  instance's sorted order (m·n values, not the layout's padded m·L): a
  scatter visit fetches the two aligned sorted blocks its layout block
  straddles and rotates them into place.
* **blocked split** (``bin_scatter_blocked_pallas`` /
  ``bin_gather_blocked_pallas``) — the split contract (tables in HBM, so
  the distributed data-axis psum can merge them between the two calls) on
  the fused kernel's visit schedule: per pass, a scalar-prefetched
  per-instance list walks only the O(n/bn + B/bt) real (point block, table
  tile) collisions of the slot-blocked layout.  The scatter schedule visits
  every tile at least once (empty tiles against an all-padding block), so
  the HBM output table is explicitly zeroed tile by tile — no tile is left
  uninitialized by the data-dependent grid.  Multi-RHS is native: the k
  columns share each mask against (1, k, bt) table blocks.

TPU layout: a block's last two dims must be multiples of (8, 128) or span
the array, so every per-point or per-slot array enters a kernel with an
explicit row axis — (m, 1, X) for one RHS or per-instance data, (m, k, X)
for a k-column block — and each grid step sees lane-dense (rows, width)
tiles.  The mask is (bt, bn): table slots on sublanes, points on lanes, so
a point block's row broadcasts down it and a scatter lands as (bt, k)
columns.  The fused kernel keeps its VMEM table tile in that column form;
the kernels whose tables live in HBM as lane-dense rows transpose one
(k, bt) tile per visit.

Every ``pallas_call`` carries a stable ``name`` (``wlsh_fused_matvec``,
``wlsh_blocked_scatter``, ...): it is the kernel's op name on a profiler
trace, so the trace reads by kernel whatever jitted function calls it.

Scalar-prefetched visit lists live in SMEM, which holds 1 MiB on v5e.  A
per-instance schedule larger than ``SMEM_SCHEDULE_BYTES`` runs as several
calls over instance groups (``_grouped_call``), all writing one output
buffer aliased from call to call; the flat route-pack schedule runs in
consecutive chunks, each resuming the tile the previous chunk left open.

``interpret`` (required on every entry point) runs a kernel in the Pallas
interpreter instead of compiling it — the CPU test path only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scalar-prefetched schedule bytes per kernel call: half of v5e's 1 MiB SMEM.
SMEM_SCHEDULE_BYTES = 512 * 1024


def _with_row_axis(a):
    """(m, X) -> (m, 1, X); (m, k, X) passes through."""
    return a[:, None, :] if a.ndim == 2 else a


def _hit(slot_ref, tile, bt):
    """(bt, bn) mask of this block's (1, bn) slot row against table tile
    ``tile``: column p is set at row slot[p] - tile·bt, and empty when the
    slot lies outside the tile."""
    slot = slot_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, slot.shape[-1]), 0)
    return row == slot - tile * bt


def _scatter(hit, contrib):
    """(k, bn) per-point contributions -> k (bt, 1) tile-load columns."""
    return [jnp.sum(jnp.where(hit, contrib[c:c + 1], 0.0), axis=1,
                    keepdims=True) for c in range(contrib.shape[0])]


def _gather(hit, cols):
    """k (bt, 1) table columns -> (k, bn) per-point reads."""
    rows = [jnp.sum(jnp.where(hit, col, 0.0), axis=0, keepdims=True)
            for col in cols]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _cols_to_rows(cols):
    """k (bt, 1) columns -> (k, bt) lane-dense rows (each column widened to
    the 8 lanes of a transposable tile)."""
    rows = [jnp.broadcast_to(c, (c.shape[0], 8)).T[0:1] for c in cols]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _rows_to_cols(rows):
    """(k, bt) lane-dense rows -> k (bt, 1) columns."""
    return [jnp.broadcast_to(rows[c:c + 1], (8, rows.shape[1])).T[:, 0:1]
            for c in range(rows.shape[0])]


def _instance_groups(m: int, per_instance_bytes: int):
    """Static (start, size) instance groups whose schedules fit the SMEM
    budget of one call."""
    g = SMEM_SCHEDULE_BYTES // per_instance_bytes
    if g < 1:
        raise ValueError(
            f"one instance's visit schedule needs {per_instance_bytes} bytes "
            f"of SMEM, over the {SMEM_SCHEDULE_BYTES}-byte budget of one "
            f"call.  A schedule grows with n/block_n + table_size/block_t "
            f"(the fused matvec at table_size = default_table_size(n) fits "
            f"up to n = 2^20 points per device); fewer points or table "
            f"slots per device fit, and chunking the visit axis is not "
            f"implemented")
    return [(s, min(g, m - s)) for s in range(0, m, g)]


def _grouped_call(body, sched, operands, specs, out_shape, *, name,
                  interpret, scratch_shapes=()):
    """Run a per-instance visit-list kernel over the grid (m, V).

    ``sched`` are (m, V) int32 schedules, scalar-prefetched.  ``specs(s)``
    returns (in_specs, out_spec) for the group starting at instance s: the
    index maps see the group-local instance i (which indexes the schedule
    rows) and address instance i + s of the operands.  One pallas_call runs
    per group; each writes its instances' rows of the single output buffer,
    which later calls take as an aliased input the body never reads.
    ``name`` is every call's kernel name on the device trace."""
    m, n_vis = sched[0].shape
    out = None
    for s, g in _instance_groups(m, 4 * len(sched) * n_vis):
        in_specs, out_spec = specs(s)
        args = [a[s:s + g] for a in sched] + list(operands)
        kernel, aliases = body, {}
        if out is not None:
            skip = len(args)
            in_specs = in_specs + [pl.BlockSpec(memory_space=pl.ANY)]
            args.append(out)
            aliases = {skip: 0}

            def kernel(*refs, skip=skip):
                body(*refs[:skip], *refs[skip + 1:])
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(sched), grid=(g, n_vis),
                in_specs=in_specs, out_specs=out_spec,
                scratch_shapes=list(scratch_shapes)),
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
            name=name,
        )(*args)
    return out


def _sorted_blocks(off, block_n, last):
    """The aligned blocks of the sorted order that the block_n values from
    offset ``off`` straddle: off // block_n and the one after it, clamped
    to the ``last`` block (lanes read from past the end carry coeff 0).

    Schedule arithmetic here and in the kernel bodies uses ``lax.div`` /
    ``lax.rem`` (offsets are non-negative): jnp's floor division lowers to
    sign fix-ups that the TPU lowering re-traces every time a program
    holding the kernel is lowered, which an eager PCG loop does per fit."""
    lo = jax.lax.div(off, block_n)
    return lo, jnp.minimum(lo + 1, last)


def _sorted_block(lo, hi, shift):
    """The bn values that start ``shift`` lanes into ``lo``: lanes
    [shift, bn) of the (k, bn) block ``lo``, then lanes [0, shift) of the
    block ``hi`` after it."""
    bn = lo.shape[-1]
    rot = jax.lax.rem(bn - shift, bn)
    lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    return jnp.where(lane < bn - shift, pltpu.roll(lo, rot, 1),
                     pltpu.roll(hi, rot, 1))


def _fused_body(v_block_ref, v_tile_phase_ref, v_off_ref, slot_ref,
                coeff_ref, beta_lo_ref, beta_hi_ref, out_ref, table_ref):
    """One visit: (point block, table tile, phase) from the prefetched lists.

    Tiles arrive in ascending order with all scatter visits before any gather
    visit, so ``table_ref`` (VMEM scratch, (bt, k) columns) is zeroed
    exactly once per tile, accumulated over the tile's scatter visits, and
    then read by its gather visits — it never round-trips through HBM.
    Padding visits re-gather the last real block against the unchanged tile
    (idempotent full overwrite).  The k RHS columns share every mask.

    A scatter visit's beta is the layout block's run of sorted points, from
    offset ``v_off`` in the instance's sorted order: the aligned blocks
    ``beta_lo_ref`` / ``beta_hi_ref`` hold it, ``v_off % bn`` lanes in.
    Lanes past the tile's points carry another point's beta and coeff 0.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    tile_phase = v_tile_phase_ref[i, j]
    tile, phase = jax.lax.div(tile_phase, 2), jax.lax.rem(tile_phase, 2)
    prev_tile = jax.lax.div(v_tile_phase_ref[i, jnp.maximum(j - 1, 0)], 2)

    @pl.when((j == 0) | (tile != prev_tile))
    def _zero():
        table_ref[...] = jnp.zeros_like(table_ref)

    hit = _hit(slot_ref, tile, table_ref.shape[0])

    @pl.when(phase == 0)
    def _scatter_visit():
        beta = _sorted_block(beta_lo_ref[...], beta_hi_ref[...],
                             jax.lax.rem(v_off_ref[i, j],
                                         beta_lo_ref.shape[-1]))
        cols = _scatter(hit, coeff_ref[...] * beta)
        for c, col in enumerate(cols):
            table_ref[:, c:c + 1] += col

    @pl.when(phase == 1)
    def _gather_visit():
        cols = [table_ref[:, c:c + 1] for c in range(table_ref.shape[1])]
        out_ref[...] = coeff_ref[...] * _gather(hit, cols)


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "interpret"))
def bin_fused_matvec_pallas(v_block, v_tile_phase, v_off, slot_lay, coeff_lay,
                            beta_sorted, *, block_n: int, block_t: int,
                            interpret: bool):
    """Fused scatter→gather over a slot-blocked layout (one kernel call).

    v_block/v_tile_phase/v_off (m, V) int32 — the per-instance visit
    schedule (scalar-prefetched; at visit j the index maps select layout
    block ``v_block[i, j]`` and the sorted beta blocks around offset
    ``v_off[i, j]``; ``v_tile_phase`` is 2·tile + phase).  slot_lay/coeff_lay
    (m, L) — the blocked layout arrays with L a multiple of ``block_n``.
    ``beta_sorted`` is (m, N) for one RHS or (m, k, N) for a k-column RHS
    block: beta in each instance's sorted order, N a multiple of
    ``block_n``.  Returns out_lay (m, L) — or (m, k, L) — f32, with
    ``out_lay[..., p] = coeff_lay[p] * table[slot_lay[p]]`` at every real
    layout position (padding positions have coeff 0).  The (m, B[, k])
    table exists only as a (block_t, 1|k) VMEM scratch tile — the k columns
    ride the same masks, so the extra HBM traffic over single-RHS is just
    beta/out themselves.
    """
    m, layout_len = slot_lay.shape
    beta3 = _with_row_axis(beta_sorted.astype(jnp.float32))
    k, sorted_len = beta3.shape[1:]
    if layout_len % block_n or sorted_len % block_n:
        raise ValueError("layout and sorted lengths must be multiples of "
                         "block_n")
    last = sorted_len // block_n - 1

    def specs(s):
        def block(i, j, vb, vtp, vo):
            return i + s, 0, vb[i, j]

        def beta_lo(i, j, vb, vtp, vo):
            return i + s, 0, _sorted_blocks(vo[i, j], block_n, last)[0]

        def beta_hi(i, j, vb, vtp, vo):
            return i + s, 0, _sorted_blocks(vo[i, j], block_n, last)[1]
        lay_spec = pl.BlockSpec((None, 1, block_n), block)
        return ([lay_spec, lay_spec,
                 pl.BlockSpec((None, k, block_n), beta_lo),
                 pl.BlockSpec((None, k, block_n), beta_hi)],
                pl.BlockSpec((None, k, block_n), block))

    out = _grouped_call(
        _fused_body, (v_block, v_tile_phase, v_off),
        (_with_row_axis(slot_lay), _with_row_axis(coeff_lay), beta3, beta3),
        specs, jax.ShapeDtypeStruct((m, k, layout_len), jnp.float32),
        name="wlsh_fused_matvec", interpret=interpret,
        scratch_shapes=[pltpu.VMEM((block_t, k), jnp.float32)])
    return out[:, 0] if beta_sorted.ndim == 2 else out


def _scatter_blocked_body(vs_block_ref, vs_tile_ref, slot_ref, contrib_ref,
                          table_ref):
    """One scatter visit of the blocked split schedule: layout block
    ``vs_block[i, j]`` accumulates into HBM table tile ``vs_tile[i, j]``.

    A tile's visits are contiguous with tiles ascending, so the revisited
    output tile stays resident between them and is zeroed exactly once, on
    its first visit — including tiles no point hashes into, which get one
    visit against the all-padding layout block (coeff 0 ⇒ adds nothing).
    The k columns of a multi-RHS block share each mask against a (k, bt)
    table block.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    tile = vs_tile_ref[i, j]
    prev_tile = vs_tile_ref[i, jnp.maximum(j - 1, 0)]

    @pl.when((j == 0) | (tile != prev_tile))
    def _zero():
        table_ref[...] = jnp.zeros_like(table_ref)

    hit = _hit(slot_ref, tile, table_ref.shape[-1])
    table_ref[...] += _cols_to_rows(_scatter(hit, contrib_ref[...]))


def _gather_blocked_body(vg_tile_ref, slot_ref, table_ref, out_ref):
    """One gather visit: layout block j reads the ONE tile it addresses.
    Every block is written exactly once, so no accumulation or init pass."""
    i, j = pl.program_id(0), pl.program_id(1)
    hit = _hit(slot_ref, vg_tile_ref[i, j], table_ref.shape[-1])
    out_ref[...] = _gather(hit, _rows_to_cols(table_ref[...]))


@functools.partial(jax.jit, static_argnames=("num_tiles", "block_n",
                                             "block_t", "interpret"))
def bin_scatter_blocked_pallas(vs_block, vs_tile, slot_lay, contrib_lay, *,
                               num_tiles: int, block_n: int, block_t: int,
                               interpret: bool):
    """Visit-list scatter over the slot-blocked layout — the split contract
    (the (m, B) table lands in HBM, psum-able) at the fused kernel's
    O(n/bn + B/bt) grid cost.

    vs_block/vs_tile (m, NB) int32 — the scatter schedule (scalar-prefetched;
    every tile visited at least once, tiles ascending and contiguous).
    slot_lay (m, L) int32 with L a multiple of ``block_n``; ``contrib_lay``
    is (m, L) for one RHS or (m, k, L) for a k-column block laid out along
    the same permutation (padding positions carry contribution 0).  Returns
    tables (m, num_tiles·block_t) f32 — or (m, k, num_tiles·block_t) — with
    tables[s, ..., b] = sum over layout positions p with slot_lay[s, p] == b
    of contrib_lay[s, ..., p].
    """
    m = slot_lay.shape[0]
    contrib3 = _with_row_axis(contrib_lay.astype(jnp.float32))
    k = contrib3.shape[1]

    def specs(s):
        def block(i, j, vb, vt):
            return i + s, 0, vb[i, j]

        def tile(i, j, vb, vt):
            return i + s, 0, vt[i, j]
        return ([pl.BlockSpec((None, 1, block_n), block),
                 pl.BlockSpec((None, k, block_n), block)],
                pl.BlockSpec((None, k, block_t), tile))

    out = _grouped_call(
        _scatter_blocked_body, (vs_block, vs_tile),
        (_with_row_axis(slot_lay), contrib3), specs,
        jax.ShapeDtypeStruct((m, k, num_tiles * block_t), jnp.float32),
        name="wlsh_blocked_scatter", interpret=interpret)
    return out[:, 0] if contrib_lay.ndim == 2 else out


@functools.partial(jax.jit, static_argnames=("block_n", "block_t",
                                             "interpret"))
def bin_gather_blocked_pallas(vg_tile, slot_lay, tables, *, block_n: int,
                              block_t: int, interpret: bool):
    """Visit-list gather over the slot-blocked layout: layout block j reads
    only the ONE table tile ``vg_tile[i, j]`` it addresses — NB grid steps
    per instance instead of the (L/bn)·(B/bt) cross product.

    tables (m, T·bt) f32 — or (m, k, T·bt) for a k-column RHS block.
    Returns out_lay of shape (m, L) — or (m, k, L) — with
    ``out_lay[s, ..., p] = tables[s, ..., slot_lay[s, p]]``.
    """
    m, layout_len = slot_lay.shape
    n_vis = vg_tile.shape[1]
    if layout_len != n_vis * block_n:
        raise ValueError("layout length must equal visits * block_n")
    tables3 = _with_row_axis(tables.astype(jnp.float32))
    k = tables3.shape[1]

    def specs(s):
        def block(i, j, vt):
            return i + s, 0, j

        def tile(i, j, vt):
            return i + s, 0, vt[i, j]
        return ([pl.BlockSpec((None, 1, block_n), block),
                 pl.BlockSpec((None, k, block_t), tile)],
                pl.BlockSpec((None, k, block_n), block))

    out = _grouped_call(
        _gather_blocked_body, (vg_tile,), (_with_row_axis(slot_lay), tables3),
        specs,
        jax.ShapeDtypeStruct((m, k, layout_len), jnp.float32),
        name="wlsh_blocked_gather", interpret=interpret)
    return out[:, 0] if tables.ndim == 2 else out


def _route_pack_body(inst_ref, blk_ref, tile_ref, flag_ref, cell_ref,
                     contrib_ref, prev_ref, out_ref):
    """One visit of the hash-join route-pack schedule (flat grid).

    The output is the flat all_to_all send buffer — ONE buffer shared by
    every instance, so the schedule is segmented by destination-cell tile
    rather than per instance: visits to a tile are contiguous in grid order,
    each tile's segment opens with a mandatory zero visit (flag 1), real
    visits (flag 0) accumulate one layout block's per-point contributions
    into the tile through the mask (duplicate (instance, slot) points hit
    the same cell — the bucket segment-sum happens inside the reduction),
    and trailing no-ops (flag 2) re-target the last tile so the final
    writebacks are idempotent.  Dropped / padding layout positions carry the
    out-of-range sentinel cell and leave empty mask columns.  A call
    runs one chunk of the schedule: when its first visit continues a tile
    segment, the tile resumes from ``prev_ref``, the buffer the previous
    chunk wrote (aliased to this call's output).
    """
    j = pl.program_id(0)
    flag = flag_ref[j]

    @pl.when(flag == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((j == 0) & (flag != 1))
    def _resume():
        out_ref[...] = prev_ref[...]

    @pl.when(flag == 0)
    def _add():
        hit = _hit(cell_ref, tile_ref[j], out_ref.shape[-1])
        out_ref[...] += _cols_to_rows(_scatter(hit, contrib_ref[...]))


def _route_unpack_body(blk_ref, tile_ref, flag_ref, cell_ref, coeff_ref,
                       back_ref, out_ref):
    """One visit of the hash-join route-unpack schedule (per-instance grid).

    Reads the received wire values back through each layout block's cell
    tile: out_lay[..., p] = coeff_lay[p] · back[cell_lay[p]].  The output is
    per-instance, so the schedule is the familiar per-instance visit list —
    a block spanning several cell tiles gets consecutive visits (zeroed on
    the first), every block is visited at least once (empty blocks against
    tile 0: all-sentinel cells gather zero), and per-instance padding visits
    (flag 2) repeat the last block so the writeback is idempotent.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    flag = flag_ref[i, j]
    first = (j == 0) | (blk_ref[i, j] != blk_ref[i, jnp.maximum(j - 1, 0)])

    @pl.when((flag == 0) & first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(flag == 0)
    def _acc():
        hit = _hit(cell_ref, tile_ref[i, j], back_ref.shape[-1])
        out_ref[...] += coeff_ref[...] * _gather(
            hit, _rows_to_cols(back_ref[...]))


@functools.partial(jax.jit, static_argnames=("num_cell_tiles", "block_n",
                                             "block_t", "interpret"))
def route_pack_pallas(p_inst, p_block, p_tile, p_flag, cell_lay, contrib_lay,
                      *, num_cell_tiles: int, block_n: int, block_t: int,
                      interpret: bool):
    """Hash-join route pack: per-point contributions -> flat send cells.

    p_inst/p_block/p_tile/p_flag (V,) int32 — the flat tile-segmented
    schedule (scalar-prefetched; see ``_route_pack_body``).  cell_lay (m, L)
    int32 destination cells along the slot-blocked layout (sentinel
    ``num_cell_tiles·block_t`` for dropped/padding positions); contrib_lay
    (m, L) f32 — or (m, k, L) for a k-column RHS block.  Returns the send
    buffer (1, num_cell_tiles·block_t) — or (k, ·) — with
    buffer[..., c] = sum over layout positions p with cell_lay[p] == c.
    """
    contrib3 = _with_row_axis(contrib_lay.astype(jnp.float32))
    k = contrib3.shape[1]

    def block(j, pi, pb, pt, pf):
        return pi[j], 0, pb[j]

    tile_spec = pl.BlockSpec((k, block_t), lambda j, pi, pb, pt, pf: (0, pt[j]))
    out_shape = jax.ShapeDtypeStruct((k, num_cell_tiles * block_t),
                                     jnp.float32)
    chunk = SMEM_SCHEDULE_BYTES // (4 * 4)
    out = jnp.zeros(out_shape.shape, jnp.float32)
    for c0 in range(0, p_inst.shape[0], chunk):
        sched = [a[c0:c0 + chunk] for a in (p_inst, p_block, p_tile, p_flag)]
        out = pl.pallas_call(
            _route_pack_body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(sched[0].shape[0],),
                in_specs=[pl.BlockSpec((None, 1, block_n), block),
                          pl.BlockSpec((None, k, block_n), block), tile_spec],
                out_specs=tile_spec),
            out_shape=out_shape,
            input_output_aliases={6: 0},
            interpret=interpret,
            name="wlsh_route_pack",
        )(*sched, _with_row_axis(cell_lay), contrib3, out)
    return out


@functools.partial(jax.jit, static_argnames=("block_n", "block_t",
                                             "interpret"))
def route_unpack_pallas(u_block, u_tile, u_flag, cell_lay, coeff_lay, back, *,
                        block_n: int, block_t: int, interpret: bool):
    """Hash-join route unpack: received wire values -> coeff-weighted layout.

    u_block/u_tile/u_flag (m, VB) int32 — the per-instance visit schedule;
    cell_lay (m, L) as in ``route_pack_pallas``; coeff_lay (m, L); ``back``
    is the padded receive buffer (1, T·block_t) f32 — or (k, T·block_t) for
    a k-column block.  Returns out_lay (m, L) — or (m, k, L) — with
    out_lay[s, ..., p] = coeff_lay[s, p] · back[..., cell_lay[s, p]]
    (sentinel cells gather 0).
    """
    m, layout_len = cell_lay.shape
    k = back.shape[0]

    def specs(s):
        def block(i, j, ub, ut, uf):
            return i + s, 0, ub[i, j]
        return ([pl.BlockSpec((None, 1, block_n), block),
                 pl.BlockSpec((None, 1, block_n), block),
                 pl.BlockSpec((k, block_t),
                              lambda i, j, ub, ut, uf: (0, ut[i, j]))],
                pl.BlockSpec((None, k, block_n), block))

    out = _grouped_call(
        _route_unpack_body, (u_block, u_tile, u_flag),
        (_with_row_axis(cell_lay), _with_row_axis(coeff_lay),
         back.astype(jnp.float32)), specs,
        jax.ShapeDtypeStruct((m, k, layout_len), jnp.float32),
        name="wlsh_route_unpack", interpret=interpret)
    return out[:, 0] if k == 1 else out
