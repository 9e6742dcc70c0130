"""Public ops: CountSketch scatter/readout built on the binning kernels.

These are the kernel-backed equivalents of the reference table primitives in
``repro.core.wlsh``.  Each dispatches on the index alone: an index carrying
the slot-blocked layout's pallas group takes the visit-list kernels, any
other index the reference primitive.

* ``bin_loads_op``   ~ ``table_loads``   — scatter signed, weighted beta into
  the (m, B) CountSketch tables (the visit-list scatter, else
  ``table_loads``'s scatter-add).
* ``bin_readout_op`` ~ ``table_readout`` — gather every point's bucket load
  back out and combine over instances (the visit-list gather, else
  ``table_readout``'s row gather).
* ``bin_fused_matvec_op`` ~ ``table_matvec_fused`` — one kernel invocation
  driven by the slot-blocked layout (``TableIndex.blocked``): scatter and
  gather share a VMEM-resident table tile, and only O(n/bn + B/bt) visits
  are scheduled per instance.

The two split ops keep the (m, B[, k]) tables in HBM between them, which is
what makes the distributed data-axis psum possible; the fused op never
writes them.  Padding (points to whole blocks, table slots to whole tiles)
is the layout's, and outputs are trimmed to logical shapes.
``interpret`` is required: the caller (``core.operator``) decides it from
the platform the program is placed on — the Pallas interpreter on CPU,
compiled kernels on TPU.

Each op names its parts in JAX's name stack, as the reference table
primitives do: the kernel call under ``KERNEL_SCOPE``, the jnp moves around
it (the gathers into and out of the slot layout, the instance mean) under
``LAYOUT_SCOPE``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.wlsh import (KERNEL_SCOPE, LAYOUT_SCOPE, TableIndex,
                          table_loads, table_readout)
from .kernel import (bin_fused_matvec_pallas, bin_gather_blocked_pallas,
                     bin_scatter_blocked_pallas)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _split_layout(index: TableIndex):
    """The slot-blocked layout when it carries the split-kernel visit
    schedules (pallas group), else None."""
    lay = getattr(index, "blocked", None)
    return lay if lay is not None and lay.vs_block is not None else None


def _beta_to_layout(lay, beta):
    """Lay beta (n,[ k]) out along the slot permutation: (m, L) or (m, k, L)
    (padding positions read the appended zero row)."""
    pad = jnp.zeros((1,) + beta.shape[1:], jnp.float32)
    beta_lay = jnp.concatenate([jnp.asarray(beta, jnp.float32), pad])[lay.src]
    return jnp.swapaxes(beta_lay, 1, 2) if beta.ndim == 2 else beta_lay


def bin_loads_blocked_op(index: TableIndex, beta, *, interpret: bool):
    """Visit-list split scatter: same (m, B[, k]) psum-able tables as
    ``bin_loads_op`` at the blocked layout's O(n/bn + B/bt) grid cost.
    Multi-RHS is native — the k columns share every one-hot tile product
    instead of re-running the kernel per column."""
    lay = _split_layout(index)
    if lay is None:
        raise ValueError("blocked split scatter needs a slot-blocked index "
                         "with the pallas group; build it with "
                         "build_blocked_layout(parts='pallas'|'both') / a "
                         "pallas-backend build_index(blocked=True)")
    with jax.named_scope(LAYOUT_SCOPE):
        beta_lay = _beta_to_layout(lay, beta)                # (m,[ k,] L)
        coeff = lay.coeff_lay if beta.ndim == 1 \
            else lay.coeff_lay[:, None, :]
        contrib = coeff * beta_lay
    with jax.named_scope(KERNEL_SCOPE):
        tables = bin_scatter_blocked_pallas(
            lay.vs_block, lay.vs_tile, lay.slot_lay, contrib,
            num_tiles=lay.num_tiles, block_n=lay.block_n,
            block_t=lay.block_t, interpret=interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        tables = tables[..., :index.table_size]
        return jnp.swapaxes(tables, 1, 2) if beta.ndim == 2 else tables


def bin_readout_blocked_op(index: TableIndex, tables, *, interpret: bool,
                           average: bool = True):
    """Visit-list split gather of (possibly psum-merged) tables: each layout
    block reads only the ONE tile it addresses; results map back to point
    order through the layout's ``inv_pos``."""
    lay = _split_layout(index)
    if lay is None:
        raise ValueError("blocked split gather needs a slot-blocked index "
                         "with the pallas group; build it with "
                         "build_blocked_layout(parts='pallas'|'both') / a "
                         "pallas-backend build_index(blocked=True)")
    multi = tables.ndim == 3
    bp = lay.num_tiles * lay.block_t
    with jax.named_scope(LAYOUT_SCOPE):
        t = jnp.swapaxes(tables, 1, 2) if multi else tables  # (m,[ k,] B)
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0),) * (t.ndim - 1) + ((0, bp - index.table_size),))
    with jax.named_scope(KERNEL_SCOPE):
        out_lay = bin_gather_blocked_pallas(
            lay.vg_tile, lay.slot_lay, t, block_n=lay.block_n,
            block_t=lay.block_t, interpret=interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        rows = jnp.arange(index.slot.shape[0], dtype=jnp.int32)[:, None]
        if multi:
            vals = jnp.swapaxes(out_lay, 1, 2)[rows, lay.inv_pos]  # (m, n, k)
            signed = vals * index.coeff[:, :, None]
        else:
            signed = out_lay[rows, lay.inv_pos] * index.coeff      # (m, n)
        return jnp.mean(signed, axis=0) if average \
            else jnp.sum(signed, axis=0)


def bin_loads_op(index: TableIndex, beta, *, interpret: bool):
    """Kernel-backed ``table_loads``: (m, B) bucket-load tables for beta (n,),
    or (m, B, k) for a (n, k) RHS block.  An index carrying the slot-blocked
    layout takes the visit-list scatter (``bin_loads_blocked_op``) at the
    layout's own geometry; otherwise the loads are ``table_loads``'s
    scatter-add.  Either way the tables are psum-able."""
    if _split_layout(index) is not None:
        return bin_loads_blocked_op(index, beta, interpret=interpret)
    return table_loads(index, beta)


def bin_readout_op(index: TableIndex, tables, *, interpret: bool,
                   average: bool = True):
    """Kernel-backed ``table_readout``: per-point loads combined over the m
    instances (mean when ``average``, else sum — the distributed path sums
    locally and divides by the global m after its psum).  ``tables`` is
    (m, B) -> (n,) out, or (m, B, k) -> (n, k).  An index carrying the
    slot-blocked layout takes the visit-list gather
    (``bin_readout_blocked_op``) at the layout's own geometry; otherwise
    each point's load is read by a direct row gather ``tables[s, slot]``
    (``table_readout``): it reads the m·n addressed entries (times k) and
    neither copies nor scans the (m, B) table."""
    if _split_layout(index) is not None:
        return bin_readout_blocked_op(index, tables, average=average,
                                      interpret=interpret)
    return table_readout(index, tables, average=average)


def bin_fused_matvec_op(index: TableIndex, beta, *, interpret: bool,
                        average: bool = True):
    """Fused one-pass WLSH table matvec off the slot-blocked layout.

    Requires ``index.blocked`` (see ``core.wlsh.build_blocked_layout``).  The
    per-iteration jnp work is one m·n gather (``beta`` into each instance's
    sorted order, ``order``) and one m·n gather back (``inv_pos``) —
    everything between runs inside a single Pallas kernel, which reads each
    layout block's beta from the sorted order and whose table tile never
    leaves VMEM.

    ``beta`` is (n,) or (n, k): a RHS block is gathered as (m, k, n) and the
    k columns share every one-hot tile product inside the kernel (see
    ``bin_fused_matvec_pallas``).
    """
    lay = index.blocked
    if lay is None or lay.order is None:
        raise ValueError("fused matvec needs a slot-blocked index with the "
                         "pallas group; build it with build_blocked_layout"
                         "(parts='pallas'|'both') / a pallas-backend "
                         "build_index(blocked=True)")
    m, n = index.slot.shape
    multi = beta.ndim == 2
    with jax.named_scope(LAYOUT_SCOPE):
        beta_sorted = jnp.asarray(beta, jnp.float32)[lay.order]
        if multi:
            beta_sorted = jnp.swapaxes(beta_sorted, 1, 2)    # (m, k, n)
        tail = _round_up(n, lay.block_n) - n
        if tail:
            beta_sorted = jnp.pad(beta_sorted, ((0, 0),) * beta.ndim
                                  + ((0, tail),))
    with jax.named_scope(KERNEL_SCOPE):
        out_lay = bin_fused_matvec_pallas(
            lay.v_block, lay.v_tile_phase, lay.v_off, lay.slot_lay,
            lay.coeff_lay, beta_sorted, block_n=lay.block_n,
            block_t=lay.block_t, interpret=interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        rows = jnp.arange(m, dtype=jnp.int32)[:, None]
        if multi:
            # (m, k, L) -> (m, n, k), coeff already applied inside the kernel
            vals = jnp.swapaxes(out_lay, 1, 2)[rows, lay.inv_pos]
        else:
            vals = out_lay[rows, lay.inv_pos]  # (m, n)
        return jnp.mean(vals, axis=0) if average else jnp.sum(vals, axis=0)
