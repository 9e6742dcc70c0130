"""Distributed WLSH-KRR: the paper's algorithm on a (pod, data, model) mesh.

Parallelization (DESIGN.md §3/§6):

* **points** are sharded over the data axes ('pod', 'data') — featurization is
  embarrassingly parallel (the LSH parameters are replicated, tiny).
* **instances** (the m independent WLSH estimators) are sharded over 'model' —
  they only interact at the final (1/m)-average.
* **bucket tables** are the only cross-shard object: each data shard scatters
  its points' signed loads into a local (m_local, B) CountSketch table, a
  single ``psum`` over the data axes merges them, and every shard reads its
  own points' loads back out.  A dense table is psum-able; the paper's
  per-bucket lists are not — that is the whole reason for the CountSketch
  adaptation.
* **CG** runs on sharded vectors; the two dot products per iteration are
  scalar psums.

All scatter/readout goes through ``core.operator.WLSHOperator`` — this module
adds only the collectives.  Each shard builds an operator from its *local*
LSH shard inside shard_map; ``loads`` produces the psum-able partial tables
and ``readout(average=False)`` the local instance-sum that the model-axis
psum completes.  Everything is expressed with ``jax.shard_map`` + ``jax.lax``
collectives; no host-side communication.
"""
from __future__ import annotations

import functools
import logging
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..backend import platform_of, resolve_backend, resolve_interpret
from ..compat import shard_map
from ..errors import SolveDivergedError, WireOverflowError
from ..testing.faults import FaultPlan, apply_wire_fault, maybe_stall
from .bucket_fns import BucketFn
from .lsh import GammaPDF, LSHParams, sample_lsh_params
from .operator import WLSHOperator
from .wlsh import RouteSchedule, build_route_schedule
from .precond import (DEFAULT_NYSTROM_RANK, PRECOND_NAMES, jacobi_precond,
                      nystrom_precond, table_diag)

Array = jnp.ndarray


class KRRStepConfig(NamedTuple):
    m: int                 # total WLSH instances (sharded over 'model')
    table_size: int        # CountSketch table slots (power of two)
    lam: float             # ridge regularizer
    cg_iters: int          # fixed PCG iteration count fused into the step
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    backend: str = "auto"  # operator backend inside each shard
    precond: str = "none"  # 'none' | 'jacobi' (any mesh) | 'nystrom'
                           # (unsharded data axes only — see make_krr_step)
    precond_rank: int = DEFAULT_NYSTROM_RANK
    overflow: str = "warn"  # hashjoin capacity-overflow policy, enforced by
                            # check_step_stats: 'raise' | 'warn' | 'allow'
    fault_plan: FaultPlan | None = None  # test-only deterministic fault
                                         # injection (repro.testing.faults)


def _shard_operator(cfg: KRRStepConfig, f: BucketFn, lsh_local: LSHParams,
                    mesh: Mesh) -> WLSHOperator:
    """Per-shard operator over the local LSH slice.  Backend and interpret
    mode follow the platform of the mesh's devices (shard_map bodies must
    see a concrete choice): a step compiled for TPU devices gets compiled
    kernels whatever the host's default backend."""
    platform = platform_of(mesh)
    return WLSHOperator(lsh=lsh_local, bucket=f, table_size=cfg.table_size,
                        backend=resolve_backend(cfg.backend, platform),
                        interpret=resolve_interpret(None, platform))


def _data_shard_count(mesh: Mesh, cfg: KRRStepConfig) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in cfg.data_axes:
        n *= sizes[a]
    return n


def make_distributed_matvec(cfg: KRRStepConfig, op: WLSHOperator, *,
                            n_data_shards: int):
    """Returns matvec(index, beta_local) -> (K~ beta)_local.

    A thin psum wrapper around the operator's local scatter/readout — must be
    called inside shard_map with an index built from the local featurization
    (m_loc, n_loc) and a (n_loc,) or (n_loc, k) beta shard (a RHS block
    rides one scatter/psum/readout round trip: the psum'd object grows to
    (m_loc, B, k) but the collective count per iteration is unchanged).
    ``n_data_shards`` is the product of the mesh's data-axis sizes
    (``_data_shard_count``) — required so a forgotten kwarg cannot silently
    disable the fused path.

    One rule picks the path: the fused one-pass matvec when the index has
    a slot-blocked layout and the data axes are unsharded, else the split
    loads → psum → readout sandwich.  With sharded data axes the table psum
    is the scatter→gather barrier, so the (m_loc, B) tables must exist
    between the two; with a single data shard (model-parallel-only meshes)
    there is nothing to merge, and the fused matvec runs locally with only
    the final model-axis psum.

    The split sandwich is itself visit-list scheduled when the index carries
    the pallas layout group: ``op.loads``/``op.readout`` dispatch to the
    blocked split kernels, which walk only the O(n/bn + B/bt) real
    collisions per pass while landing the same psum-able (m_loc, B[, k])
    tables in HBM.
    """
    def matvec(index, beta_local):
        if n_data_shards == 1 and getattr(index, "blocked", None) is not None:
            out = op.matvec(index, beta_local, average=False)
        else:
            tables = jax.lax.psum(op.loads(index, beta_local), cfg.data_axes)
            out = op.readout(index, tables, average=False)  # sum over m_loc
        return jax.lax.psum(out, cfg.model_axis) / cfg.m
    return matvec


def _sharded_dot(a: Array, b: Array, axes: Sequence[str]) -> Array:
    """Column-wise sharded inner product: scalar for (n_loc,) operands,
    (k,) for (n_loc, k) RHS blocks — one scalar/vector psum either way."""
    return jax.lax.psum(jnp.sum(a * b, axis=0), axes)


def _bcast(c: Array, v: Array) -> Array:
    """Broadcast a per-column coefficient over v (n,) or (n, k)."""
    return c * v if v.ndim == 1 else c[None, :] * v


def _colmask(c: Array, v: Array) -> Array:
    """Shape a per-column bool mask for a where() over v (n,) or (n, k)."""
    return c if v.ndim == 1 else c[None, :]


def cg_iterations(matvec, y_local: Array, cfg: KRRStepConfig,
                  precond_apply=None):
    """Fixed-iteration PCG on (K~ + lam I) beta = y, vectors data-sharded.
    ``y_local`` is (n_loc,) or an (n_loc, k) RHS block — the recurrences run
    column-wise so every column follows its own single-RHS trajectory while
    sharing each matvec and collective.  ``precond_apply`` (z = P⁻¹ r on
    local shards, e.g. the Jacobi diagonal from ``make_krr_step``) defaults
    to identity, which reduces exactly to plain CG.  Returns
    (beta_local, resnorm) with resnorm per column for a block.

    Non-finite sentinel: a poisoned step (NaN/Inf wire cell reaching the
    matvec, non-finite target column) deactivates its column BEFORE the bad
    update lands — (x, r) freeze at the last finite iterate and the column's
    resnorm reports NaN.  The host-side runner (``run_krr_step_resilient``)
    turns that sentinel into a bf16→f32 wire retry or a structured
    ``SolveDivergedError`` instead of silently-garbage betas."""
    lam = jnp.asarray(cfg.lam, jnp.float32)
    identity = precond_apply is None
    psolve = (lambda r: r) if identity else precond_apply

    def amv(v):
        return matvec(v) + lam * v

    def residual_dots(r, z):
        # with the identity preconditioner rho == ||r||², so plain CG keeps
        # its two psums per iteration (no third collective sneaks in)
        rs = _sharded_dot(r, r, cfg.data_axes)
        return (rs, rs) if identity else \
            (_sharded_dot(r, z, cfg.data_axes), rs)

    x = jnp.zeros_like(y_local)
    r = y_local - amv(x)
    z = psolve(r)
    rho, rs = residual_dots(r, z)
    dead = ~(jnp.isfinite(rho) & jnp.isfinite(rs))
    p = jnp.where(_colmask(~dead, z), z, 0.0)

    def body(_, state):
        x, r, p, rho, rs, dead = state
        ap = amv(p)
        alpha = rho / jnp.maximum(_sharded_dot(p, ap, cfg.data_axes), 1e-30)
        # sentinel: a non-finite step deactivates its column for good — the
        # where() both forces the step to 0 AND blocks 0·NaN from reaching x
        ok = jnp.isfinite(alpha) & ~dead
        dead = dead | ~jnp.isfinite(alpha)
        okb = _colmask(ok, p)
        alpha = jnp.where(ok, alpha, 0.0)
        x = x + jnp.where(okb, _bcast(alpha, p), 0.0)
        r = r - jnp.where(okb, _bcast(alpha, ap), 0.0)
        z = psolve(r)
        rho_new, rs_new = residual_dots(r, z)
        bad = ~(jnp.isfinite(rho_new) & jnp.isfinite(rs_new))
        dead = dead | bad
        live = ~dead
        beta = jnp.where(live, rho_new / jnp.maximum(rho, 1e-30), 0.0)
        p = jnp.where(_colmask(live, p), z + _bcast(beta, p), 0.0)
        rho = jnp.where(live, rho_new, rho)
        rs = jnp.where(live, rs_new, rs)
        return x, r, p, rho, rs, dead

    x, r, p, rho, rs, dead = jax.lax.fori_loop(0, cfg.cg_iters, body,
                                               (x, r, p, rho, rs, dead))
    return x, jnp.where(dead, jnp.nan, jnp.sqrt(rs))


def _shard_preconditioner(cfg: KRRStepConfig, mv, idx):
    """Build cfg.precond inside shard_map; returns apply(r_local) or None.

    ``mv`` may be None when the caller has already rejected 'nystrom'
    (the hash-join step does — jacobi never touches the matvec).

    * jacobi — diag(K̃)_i = mean_s coeff²[s, i] is per-point, so the local
      column sums only need the model-axis psum; the apply is elementwise on
      the local shard (no extra collectives per iteration).
    * nystrom — needs K̃-columns for its pivot block, i.e. a global matvec
      with global one-hot columns.  With unsharded data axes the local index
      IS global (only the model psum participates), so the single-host
      factorization from core/precond.py traces directly; with sharded data
      axes pivot selection/column exchange would need a gather we don't
      ship yet, so make_krr_step rejects that combination up front.
    """
    if cfg.precond in ("none", None):
        return None
    diag = jax.lax.psum(table_diag(idx.coeff, average=False),
                        cfg.model_axis) / cfg.m
    if cfg.precond == "jacobi":
        return jacobi_precond(diag, cfg.lam).apply
    if cfg.precond == "nystrom":
        pre = nystrom_precond(lambda v: mv(idx, v), diag, cfg.lam,
                              cfg.precond_rank)
        return pre.apply
    raise ValueError(f"unknown preconditioner {cfg.precond!r}; "
                     f"expected one of {PRECOND_NAMES}")


def make_krr_step(mesh: Mesh, cfg: KRRStepConfig, f: BucketFn):
    """Builds the jit-able distributed KRR training step.

    step(x, y, lsh) -> (beta, resnorm, tables)
      x (n, d) sharded P(data_axes, None); y sharded P(data_axes) — (n,) for
      one target or (n, k) for a RHS block (batched KRR / GP posterior
      samples; the k columns share every matvec and collective)
      lsh: LSHParams with leading m dim sharded P(model_axis)
    The returned beta is sharded like y; tables (m, B[, k]) are the
    prediction data structure (model-sharded, data-replicated).

    ``cfg.precond`` runs the solve as PCG: 'jacobi' works on any mesh (its
    diagonal is a model-axis psum; the apply is shard-local); 'nystrom'
    requires unsharded data axes — its pivot columns come from global
    matvecs — and raises otherwise.
    """
    data_spec = P(cfg.data_axes)
    in_specs = (P(cfg.data_axes, None), data_spec,
                LSHParams(w=P(cfg.model_axis, None), z=P(cfg.model_axis, None),
                          r1=P(cfg.model_axis, None), r2=P(cfg.model_axis, None)))
    out_specs = (data_spec, P(), P(cfg.model_axis, None))
    n_data = _data_shard_count(mesh, cfg)
    # one data shard runs the fused matvec off the layout; sharded data axes
    # keep the split (psum-able) sandwich, whose pallas scatter/gather still
    # follow the layout's visit lists — only the reference split ignores it
    want_blocked = n_data == 1 or \
        resolve_backend(cfg.backend, platform_of(mesh)) == "pallas"
    if cfg.precond == "nystrom" and n_data != 1:
        raise ValueError(
            "precond='nystrom' needs unsharded data axes (its pivot columns "
            "are global K~ matvecs); use 'jacobi' on data-sharded meshes")

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def step(x_local, y_local, lsh_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        idx = op.build_index(op.featurize(x_local), blocked=want_blocked)
        mv = make_distributed_matvec(cfg, op, n_data_shards=n_data)
        pre = _shard_preconditioner(cfg, mv, idx)
        beta_local, resnorm = cg_iterations(lambda v: mv(idx, v), y_local,
                                            cfg, precond_apply=pre)
        # final prediction tables for the solved beta
        tables = jax.lax.psum(op.loads(idx, beta_local), cfg.data_axes)
        return beta_local, resnorm, tables

    return step


def make_krr_predict(mesh: Mesh, cfg: KRRStepConfig, f: BucketFn):
    """predict(x_test, lsh, tables) -> yhat; test points data-sharded.

    A pallas-backend predict builds the slot-blocked layout and gathers
    through the visit-list kernels.  A layout-less index would read its
    loads by a direct row gather (``bin_readout_op``), which needs no slot
    sort.  Reference-backend prediction skips the layout: its readout never
    consults it, so the sort would be wasted.
    """
    want_blocked = resolve_backend(cfg.backend, platform_of(mesh)) == "pallas"
    in_specs = (P(cfg.data_axes, None),
                LSHParams(w=P(cfg.model_axis, None), z=P(cfg.model_axis, None),
                          r1=P(cfg.model_axis, None), r2=P(cfg.model_axis, None)),
                P(cfg.model_axis, None))
    out_specs = P(cfg.data_axes)

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def predict(x_local, lsh_local, tables_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        idx = op.build_index(op.featurize(x_local), blocked=want_blocked)
        out = op.readout(idx, tables_local, average=False)
        return jax.lax.psum(out, cfg.model_axis) / cfg.m

    return predict


def sample_sharded_lsh(key: jax.Array, m: int, d: int, pdf: GammaPDF,
                       lengthscale: float = 1.0) -> LSHParams:
    """Host-side LSH parameter sampling (tiny; replicate then shard)."""
    return sample_lsh_params(key, m, d, pdf, lengthscale)


# ---------------------------------------------------------------------------
# BEYOND-PAPER: hash-join table mode
# ---------------------------------------------------------------------------
#
# The psum of the (m_loc, B) CountSketch tables moves O(B) floats per CG
# iteration per chip even though each shard contributes and reads only
# O(n_local) nonzeros.  The hash join shards the TABLE over the data axes
# (each shard owns B/n_shards slots) and routes only the nonzeros:
#
#   scatter:  (slot, contrib) pairs -> owner shard  (all_to_all, ~n_local f32)
#   readout:  slot requests -> owner -> values back (all_to_all, precomputed
#             routing: slots are fixed for the whole CG solve)
#
# Collective bytes per iteration drop from m_loc*B*4 to ~2*capacity*n_local*4
# — 16x at the krr_4m cell (measured; see EXPERIMENTS.md §Perf).  Entries
# beyond the per-destination capacity are dropped (probability ~0 for
# capacity_factor >= 2 with uniform hashing; the estimator stays unbiased in
# sign expectation, and tests compare against the exact table mode).
#
# The routing is built off the slot-blocked layout's per-instance stable
# slot sort (core/wlsh.py): owner shards are slot//spp, so owner grouping
# falls out of the already-sorted slot order — no second argsort — and
# duplicate (instance, slot) pairs collapse to ONE routed cell per distinct
# bucket (contributions pre-summed by the layout's segment ids before they
# touch the wire; values broadcast back through the same ids).  The wire
# payload is the deduplicated slot set, never more than the owner's
# m_loc·spp table cells.
#
# This path's scatter/readout is NOT the operator's dense-table primitive —
# it is a different algorithm (table sharded over data, all_to_all routing),
# so only featurization/indexing is shared with the operator.

class _RoutePlan(NamedTuple):
    """Pallas route-kernel driver: destination cells along the slot-blocked
    layout plus the pack/unpack visit schedules (core.wlsh.RouteSchedule)."""
    cell_lay: Array    # (m, L) int32 — wire cell per layout position
                       #   (sentinel = num_cell_tiles·block_t)
    sched: RouteSchedule


class _Routing(NamedTuple):
    pt_cell: Array     # (m_loc, n_loc) destination wire cell per point (its
                       #   bucket's cell at the owner; sentinel NB = dropped)
    recv_ids: Array    # (NB,) owner-side (inst·spp + slot%spp) table ids per
                       #   received cell (sentinel m_loc·spp = empty cell)
    serve_map: Array   # (n_shards, NB) flat recv positions holding each wire
                       #   cell's table id in sender run r (sentinel NB =
                       #   absent) — the per-iteration serve is s gathers
                       #   through this map instead of a table scatter+gather
    spp: int           # slots per shard
    cap: int           # bucket capacity per destination shard
    dropped: Array     # scalar int32 — distinct buckets past capacity on
                       #   THIS shard (overflow accounting, same pack pass)
    plan: _RoutePlan | None = None   # pallas backends only


def _routing_maps(slot: Array, lay, n_shards: int, table_size: int,
                  cap_factor: float):
    """Pure half of the routing build (no collectives — unit-lowerable):
    derive the segment <-> cell maps and per-destination slot requests from
    the layout's slot sort.  Contains NO sort: owners ascend with the
    already-sorted slots, so group starts come from ``searchsorted`` and
    in-group ranks from the layout's segment ids."""
    m_loc, n_loc = slot.shape
    e = m_loc * n_loc
    spp = table_size // n_shards
    cap = max(8, int(-(-e * cap_factor // n_shards) // 8 * 8))
    # a cell is a distinct (instance, slot) pair at its owner: never more
    # than the owner's m_loc*spp table cells (exact => dedup cannot drop)
    cap = min(cap, m_loc * spp)
    nb = n_shards * cap

    inst = jnp.arange(m_loc, dtype=jnp.int32)[:, None]
    ss = jnp.take_along_axis(slot, lay.perm, axis=1)          # sorted slots
    owner = (ss // spp).astype(jnp.int32)                     # ascending rows
    is_first = jnp.concatenate(
        [jnp.ones((m_loc, 1), bool), ss[:, 1:] != ss[:, :-1]], axis=1)
    # distinct buckets per (instance, owner) and their cross-instance offsets
    ucount = jnp.zeros((m_loc, n_shards), jnp.int32).at[inst, owner].add(
        is_first.astype(jnp.int32))
    off = jnp.cumsum(ucount, axis=0) - ucount                 # exclusive
    # rank of each distinct bucket inside its (instance, owner) group:
    # segment id minus the segment id at the owner group's first position
    fpos = jax.vmap(lambda o: jnp.searchsorted(
        o, jnp.arange(n_shards, dtype=o.dtype)))(owner)
    fpos = jnp.minimum(fpos, n_loc - 1).astype(jnp.int32)
    first_seg = jnp.take_along_axis(lay.seg_id, fpos, axis=1)  # (m, S)
    rank = lay.seg_id - first_seg[inst, owner]
    pos = off[inst, owner] + rank
    keep = is_first & (pos < cap)
    # overflow accounting rides the SAME pack pass: every distinct bucket
    # whose in-owner rank fell past the capacity is a dropped contribution
    dropped = jnp.sum(is_first & (pos >= cap), dtype=jnp.int32)
    # build-time load observability: distinct cells bound for each owner
    # (summed over my local instances) — max vs cap is the headroom signal
    owner_max = jnp.max(jnp.sum(ucount, axis=0)).astype(jnp.int32)
    cell = jnp.where(keep, owner * cap + pos, nb)              # (m, n)
    flat_seg = inst * n_loc + lay.seg_id                       # (m, n)
    useg_cell = jnp.full((e,), nb, jnp.int32).at[
        jnp.where(keep, flat_seg, e).reshape(-1)].set(
        cell.reshape(-1), mode="drop")
    # broadcast each bucket's cell back to its points: pt_cell is the ONLY
    # per-iteration map — route-pack scatter-adds contributions through it
    # (the bucket segment-sum happens inside the scatter-add) and
    # route-unpack gathers received values back through it
    pt_cell = useg_cell[inst * n_loc + lay.seg_pt]             # (m, n)
    packed = inst * spp + (ss % spp).astype(jnp.int32)
    send_packed = jnp.full((nb,), -1, jnp.int32).at[cell.reshape(-1)].set(
        packed.reshape(-1), mode="drop").reshape(n_shards, cap)
    return pt_cell, send_packed, spp, cap, dropped, owner_max


# destination-cell tile width for the route kernels (matches the table tile
# width of the binning kernels; cells are wire positions, not table slots)
ROUTE_BLOCK_T = 512

_LOG = logging.getLogger("repro.distributed")


def _log_routing_build(owner_max, *, cap: int, n_shards: int) -> None:
    over = int(owner_max) > cap
    _LOG.log(logging.WARNING if over else logging.INFO,
             "hashjoin routing: max %d cells/owner vs capacity %d "
             "(%d shard(s))%s", int(owner_max), cap, n_shards,
             " — OVERFLOW, distinct buckets will be dropped" if over else "")
    # runs via jax.debug.callback with CONCRETE values at execution time —
    # the capacity-headroom signal on the live endpoint, not just the log
    obs.counter("hashjoin_routing_builds_total",
                "hash-join routing tables built").inc()
    obs.gauge("hashjoin_route_cap",
              "per-owner cell capacity of the last routing build").set(cap)
    obs.gauge("hashjoin_route_owner_max",
              "max observed cells/owner in the last routing build"
              ).set(int(owner_max))


def _make_route_plan(pt_cell: Array, lay, nb: int) -> _RoutePlan:
    """Lay the per-point wire cells out along the slot-blocked layout and
    build the pack/unpack visit schedules.  Cells ascend with the layout's
    slot sort (owner, then in-owner rank), which is exactly the monotonicity
    ``build_route_schedule`` needs; dropped points and padding positions map
    to the kernels' out-of-range sentinel."""
    m_loc, n_loc = pt_cell.shape
    cb = -(-nb // ROUTE_BLOCK_T)
    sentinel = cb * ROUTE_BLOCK_T
    rows = jnp.arange(m_loc, dtype=jnp.int32)[:, None]
    ptc_pad = jnp.concatenate(
        [pt_cell, jnp.full((m_loc, 1), nb, jnp.int32)], axis=1)
    cell_lay = ptc_pad[rows, lay.src]                          # (m, L)
    cell_lay = jnp.where(cell_lay < nb, cell_lay, sentinel).astype(jnp.int32)
    sched = build_route_schedule(cell_lay, num_cell_tiles=cb,
                                 block_n=lay.block_n, block_t=ROUTE_BLOCK_T)
    return _RoutePlan(cell_lay=cell_lay, sched=sched)


def _build_routing(slot: Array, lay, n_shards: int, table_size: int,
                   data_axes, cap_factor: float, *,
                   kernels: bool = False) -> _Routing:
    """Precompute the point <-> wire-cell maps and exchange slot requests.
    slot (m_loc, n_loc); ``lay`` is the slot-blocked layout (reference
    group; plus the pallas group when ``kernels`` asks for the route-kernel
    schedules).  Runs once per CG solve (slots are fixed).

    The max observed cells-per-owner is logged at build time (INFO on the
    ``repro.distributed`` logger) — the headroom signal for ``cap_factor``
    tuning, surfaced BEFORE any overflow silently drops mass."""
    pt_cell, send_packed, spp, cap, dropped, owner_max = _routing_maps(
        slot, lay, n_shards, table_size, cap_factor)
    jax.debug.callback(functools.partial(_log_routing_build, cap=cap,
                                         n_shards=n_shards), owner_max)
    recv_packed = jax.lax.all_to_all(send_packed, data_axes, 0, 0,
                                     tiled=True).reshape(-1)
    m_loc = slot.shape[0]
    recv_ids = jnp.where(recv_packed >= 0, recv_packed,
                         m_loc * spp).astype(jnp.int32)
    # serve map: each sender run of recv_ids is sorted (instance-major,
    # slot-ascending pack order; sentinels trail), so the position of any
    # table id inside run r is one searchsorted — NO sort, and the
    # per-iteration segment-sum across runs becomes s vectorized gathers
    # (XLA CPU scatters are scalar loops; this was the iteration hot spot)
    nb = n_shards * cap
    ids2 = recv_ids.reshape(n_shards, cap)
    pos = jax.vmap(lambda row: jnp.searchsorted(row, recv_ids))(ids2)
    pos = jnp.minimum(pos, cap - 1).astype(jnp.int32)
    hit = (jnp.take_along_axis(ids2, pos, axis=1) == recv_ids[None]) \
        & (recv_ids < m_loc * spp)[None]
    serve_map = jnp.where(
        hit, jnp.arange(n_shards, dtype=jnp.int32)[:, None] * cap + pos, nb)
    plan = _make_route_plan(pt_cell, lay, nb) if kernels else None
    return _Routing(pt_cell=pt_cell, recv_ids=recv_ids, serve_map=serve_map,
                    spp=spp, cap=cap, dropped=dropped, plan=plan)


def _hashjoin_send(rt: _Routing, lay, coeff: Array, beta_local: Array,
                   payload_dtype, interpret: bool,
                   plan: FaultPlan | None = None) -> Array:
    """Route pack: per-point contributions -> (n_shards, cap[, k]) payload.

    One flat scatter-add through ``pt_cell`` (flat-XLA fallback) or one
    Pallas route-pack kernel call (``rt.plan``) — the per-bucket segment
    sum happens inside the cell accumulation, so the old per-iteration
    vmap'd ``segment_sum`` + cell scatter pair collapses into one op.
    Cast to the wire dtype happens once, after the f32 accumulation.
    ``plan`` (tests only) drops/poisons wire cells AFTER the cast — the
    fault rides the all_to_all exactly as a flaky link would inject it."""
    multi = beta_local.ndim == 2
    tail = beta_local.shape[1:]
    nb = rt.recv_ids.shape[0]
    n_shards = nb // rt.cap
    if rt.plan is None:
        contrib = (coeff[:, :, None] * beta_local[None] if multi
                   else coeff * beta_local[None, :])
        # dropped/overflow points carry the sentinel cell id nb — out of
        # bounds for the (nb,) buffer, so mode="drop" discards them without
        # the extra sentinel row + [:nb] slice pass over the wire buffer
        send = jnp.zeros((nb,) + tail, jnp.float32).at[
            rt.pt_cell.reshape(-1)].add(
            contrib.reshape((-1,) + tail), mode="drop")
    else:
        from ..kernels.binning import route_pack_pallas
        sched = rt.plan.sched
        # lay.src sentinel (== n_loc) is out of bounds -> pad rows read 0
        beta_lay = jnp.asarray(beta_local, jnp.float32).at[
            lay.src].get(mode="fill", fill_value=0)
        if multi:
            beta_lay = jnp.swapaxes(beta_lay, 1, 2)            # (m, k, L)
            contrib_lay = lay.coeff_lay[:, None, :] * beta_lay
        else:
            contrib_lay = lay.coeff_lay * beta_lay
        packed = route_pack_pallas(
            sched.p_inst, sched.p_block, sched.p_tile, sched.p_flag,
            rt.plan.cell_lay, contrib_lay,
            num_cell_tiles=sched.num_cell_tiles, block_n=lay.block_n,
            block_t=sched.block_t, interpret=interpret)
        send = packed[:, :nb].T if multi else packed[0, :nb]
    wire = send.astype(payload_dtype).reshape((n_shards, rt.cap) + tail)
    return apply_wire_fault(plan, wire)


def _hashjoin_loads(rt: _Routing, lay, coeff: Array, beta_local: Array,
                    data_axes, m_loc: int, payload_dtype,
                    interpret: bool,
                    plan: FaultPlan | None = None) -> tuple[Array, Array]:
    """Pack + all_to_all + owner scatter-add: MY (m_loc·spp[, k]) f32 table
    shard.  One wire value per distinct (instance, slot) pair; empty cells
    carry the sentinel id and are dropped by the scatter.

    Returns ``(table, nonfinite)``: non-finite received cells are ZEROED
    before they can poison a table slot (a NaN slot would NaN every future
    prediction touching it) and counted — the count feeds ``StepStats`` so
    the policy layer can warn/raise instead of serving silently-wrong
    loads."""
    tail = beta_local.shape[1:]
    nb = rt.recv_ids.shape[0]
    send = _hashjoin_send(rt, lay, coeff, beta_local, payload_dtype,
                          interpret, plan)
    recv = jax.lax.all_to_all(send, data_axes, 0, 0, tiled=True)
    recv_flat = recv.reshape((nb,) + tail).astype(jnp.float32)
    finite = jnp.isfinite(recv_flat)
    nonfinite = jnp.sum(~finite, dtype=jnp.int32)
    recv_flat = jnp.where(finite, recv_flat, 0.0)
    table = jnp.zeros((m_loc * rt.spp,) + tail, jnp.float32).at[
        rt.recv_ids].add(recv_flat, mode="drop")
    return table, nonfinite


def _hashjoin_readout(rt: _Routing, lay, coeff: Array, table: Array,
                      data_axes, model_axis, m_total: int, payload_dtype,
                      interpret: bool,
                      plan: FaultPlan | None = None) -> Array:
    """Serve the fixed slot requests from my table shard, all_to_all the
    values back, and unpack (``_hashjoin_return``).  This is the
    materialized-table path — prediction against a stored shard.  The
    return hop sanitizes non-finite wire cells (``sanitize=True``): a
    poisoned prediction exchange degrades to dropped bucket mass, it never
    emits a NaN prediction."""
    # recv_ids sentinel (== m_loc·spp) is out of bounds -> empty wire cells
    # serve 0, with no per-iteration sentinel-row concat over the table
    served = table.at[rt.recv_ids].get(mode="fill", fill_value=0)
    return _hashjoin_return(rt, lay, coeff, served, data_axes, model_axis,
                            m_total, payload_dtype, interpret, plan=plan,
                            sanitize=True)


def _hashjoin_return(rt: _Routing, lay, coeff: Array, served: Array,
                     data_axes, model_axis, m_total: int, payload_dtype,
                     interpret: bool, plan: FaultPlan | None = None,
                     sanitize: bool = False) -> Array:
    """all_to_all the served (NB[, k]) wire-cell values back and unpack:
    out = psum_model(sum_s coeff · back[pt_cell]) / m.  The unpack is one
    flat gather + coeff reduce (flat-XLA) or one Pallas route-unpack kernel
    call; dropped cells gather 0 both ways.

    ``sanitize`` zeroes non-finite received cells (prediction path: a fault
    degrades to dropped mass).  The CG matvec path leaves them in — the
    solver's residual sentinel is the detection signal there, and zeroing
    would hide the divergence."""
    multi = served.ndim == 2
    tail = served.shape[1:]
    nb = rt.recv_ids.shape[0]
    n_shards = nb // rt.cap
    m_loc = coeff.shape[0]
    wire = apply_wire_fault(
        plan, served.astype(payload_dtype).reshape((n_shards, rt.cap) + tail))
    back = jax.lax.all_to_all(wire, data_axes, 0, 0, tiled=True)
    back_flat = back.reshape((nb,) + tail).astype(jnp.float32)
    if sanitize:
        back_flat = jnp.where(jnp.isfinite(back_flat), back_flat, 0.0)
    if rt.plan is None:
        # pt_cell sentinel (== nb) out of bounds -> dropped points read 0
        vals = back_flat.at[rt.pt_cell].get(
            mode="fill", fill_value=0)                         # (m, n[, k])
        contrib = coeff[:, :, None] * vals if multi else coeff * vals
        out = jnp.sum(contrib, axis=0)
    else:
        from ..kernels.binning import route_unpack_pallas
        sched = rt.plan.sched
        cbbt = sched.num_cell_tiles * sched.block_t
        buf = jnp.pad(back_flat, ((0, cbbt - nb),) + ((0, 0),) * len(tail))
        buf = buf.T if multi else buf[None]                    # (1|k, CBbt)
        out_lay = route_unpack_pallas(
            sched.u_block, sched.u_tile, sched.u_flag, rt.plan.cell_lay,
            lay.coeff_lay, buf, block_n=lay.block_n, block_t=sched.block_t,
            interpret=interpret)
        rows = jnp.arange(m_loc, dtype=jnp.int32)[:, None]
        if multi:
            if out_lay.ndim == 2:                              # k == 1
                out_lay = out_lay[:, None, :]
            out = jnp.swapaxes(out_lay, 1, 2)[rows, lay.inv_pos].sum(axis=0)
        else:
            out = out_lay[rows, lay.inv_pos].sum(axis=0)
    return jax.lax.psum(out, model_axis) / m_total


def _hashjoin_matvec(rt: _Routing, lay, coeff: Array, m_total: int,
                     data_axes, model_axis, beta_local: Array,
                     payload_dtype, interpret: bool,
                     plan: FaultPlan | None = None):
    """One hash-join K~ matvec: pack -> a2a -> serve -> a2a -> unpack ->
    model psum.  The serve never materializes the owner's table: each wire
    cell's aggregate is the cross-run segment-sum of the received payloads,
    read through the precomputed ``serve_map`` as s vectorized gathers
    (the table scatter-add runs ONCE per solve, for the returned prediction
    table — not per iteration).  payload_dtype=bfloat16 halves the wire
    bytes; contributions accumulate in f32 and round ONCE at each a2a
    boundary — noise is one bf16 rounding per distinct (instance, slot) per
    hop, not per point (CG tolerates it; tests pin the accuracy).  ``coeff``
    is the index's precomputed weight·sign (m_loc, n_loc)."""
    tail = beta_local.shape[1:]
    nb = rt.recv_ids.shape[0]
    send = _hashjoin_send(rt, lay, coeff, beta_local, payload_dtype,
                          interpret, plan)
    recv = jax.lax.all_to_all(send, data_axes, 0, 0, tiled=True)
    recv_flat = recv.reshape((nb,) + tail).astype(jnp.float32)
    served = recv_flat.at[rt.serve_map[0]].get(mode="fill", fill_value=0)
    for r in range(1, rt.serve_map.shape[0]):
        served = served + recv_flat.at[rt.serve_map[r]].get(
            mode="fill", fill_value=0)
    return _hashjoin_return(rt, lay, coeff, served, data_axes, model_axis,
                            m_total, payload_dtype, interpret)


def _hashjoin_layout_parts(backend: str) -> str:
    """The routing build consumes the layout's reference group; the route
    kernels additionally need the pallas group (src/coeff_lay/inv_pos)."""
    return "both" if backend == "pallas" else "reference"


class StepStats(NamedTuple):
    """Global fault counters from one hash-join step, psum'd over every mesh
    axis (replicated — tiny int32 scalars).  ``check_step_stats`` turns them
    into the configured policy action on the host."""

    overflow_dropped: Array   # distinct buckets dropped past routing capacity
    wire_nonfinite: Array     # non-finite wire cells zeroed in the final
                              # (f32) table exchange


OVERFLOW_POLICIES = ("raise", "warn", "allow")


def check_step_stats(stats: StepStats, *, overflow: str = "warn") -> None:
    """Host-side policy gate for a completed hash-join step (raising inside
    the traced step is impossible — the counters come out as outputs).

    overflow='raise' turns dropped buckets OR zeroed non-finite wire cells
    into a structured ``WireOverflowError``; 'warn' warns once per call;
    'allow' documents that dropped mass is acceptable (the estimator stays
    unbiased in sign expectation — see the hash-join module comment)."""
    if overflow not in OVERFLOW_POLICIES:
        raise ValueError(f"overflow must be one of {OVERFLOW_POLICIES}, "
                         f"got {overflow!r}")
    dropped = int(np.asarray(stats.overflow_dropped))
    nonfinite = int(np.asarray(stats.wire_nonfinite))
    # StepStats re-expressed on the registry: the NamedTuple stays the
    # step's API, the counters make the faults scrapeable across steps
    obs.counter("hashjoin_steps_checked_total",
                "hash-join steps run through the fault-policy gate").inc()
    if dropped:
        obs.counter("hashjoin_overflow_dropped_total",
                    "distinct buckets dropped past routing capacity"
                    ).inc(dropped)
    if nonfinite:
        obs.counter("hashjoin_wire_nonfinite_total",
                    "non-finite wire cells zeroed in table exchanges"
                    ).inc(nonfinite)
    if dropped == 0 and nonfinite == 0:
        return
    msg = (f"hashjoin step dropped {dropped} distinct bucket(s) past the "
           f"routing capacity and zeroed {nonfinite} non-finite wire "
           f"cell(s); raise cap_factor or investigate the payload")
    if overflow == "raise":
        raise WireOverflowError(msg, dropped=dropped)
    if overflow == "warn":
        warnings.warn(msg, RuntimeWarning, stacklevel=2)


def run_krr_step_resilient(mesh: Mesh, cfg: KRRStepConfig, f: BucketFn,
                           x, y, lsh, *, cap_factor: float = 2.0,
                           payload_dtype=jnp.bfloat16):
    """Run the hash-join step with the full recovery ladder (DESIGN.md §9):

    1. execute with the configured wire dtype,
    2. apply the ``cfg.overflow`` policy to the step's fault counters,
    3. on a non-finite solve (NaN resnorm sentinel from ``cg_iterations``)
       retry ONCE with an f32 wire — bf16's coarser grid is the usual
       suspect and the retry costs one extra step execution,
    4. still non-finite → structured ``SolveDivergedError`` (never return
       silently-garbage betas).

    Returns (beta, resnorm, table, stats) like ``make_krr_step_hashjoin``.
    Host-side by construction (the policy check syncs the counters), so use
    it from drivers — not inside jit."""
    step = jax.jit(make_krr_step_hashjoin(mesh, cfg, f,
                                          cap_factor=cap_factor,
                                          payload_dtype=payload_dtype))
    with obs.span("dist.krr_step", {"wire": jnp.dtype(payload_dtype).name},
                  to_histogram=obs.histogram(
                      "dist_krr_step_us",
                      "resilient hash-join step wall time")):
        beta, resnorm, table, stats = step(x, y, lsh)
        jax.block_until_ready(resnorm)
    check_step_stats(stats, overflow=cfg.overflow)
    retried = False
    if not bool(jnp.all(jnp.isfinite(resnorm))):
        if payload_dtype == jnp.bfloat16:
            warnings.warn("non-finite CG residual on the bf16 wire; "
                          "retrying once with an f32 wire",
                          RuntimeWarning, stacklevel=2)
            obs.counter("dist_wire_retry_total",
                        "bf16 wire solves retried on an f32 wire").inc()
            retried = True
            step32 = jax.jit(make_krr_step_hashjoin(
                mesh, cfg, f, cap_factor=cap_factor,
                payload_dtype=jnp.float32))
            with obs.span("dist.krr_step", {"wire": "float32"}):
                beta, resnorm, table, stats = step32(x, y, lsh)
                jax.block_until_ready(resnorm)
            check_step_stats(stats, overflow=cfg.overflow)
        if not bool(jnp.all(jnp.isfinite(resnorm))):
            obs.counter("dist_solve_diverged_total",
                        "distributed solves abandoned after all retries"
                        ).inc()
            raise SolveDivergedError(
                "distributed CG residual non-finite"
                + (" (f32 wire retry included)" if retried else ""),
                resnorm=np.asarray(resnorm),
                fallbacks=("wire:bf16->f32",) if retried else ())
    return beta, resnorm, table, stats


def make_krr_step_hashjoin(mesh: Mesh, cfg: KRRStepConfig, f: BucketFn, *,
                           cap_factor: float = 2.0,
                           payload_dtype=jnp.bfloat16):
    """Hash-join variant of make_krr_step (same signature; returns
    (beta, resnorm, table_shard, stats) with the table SHARDED over data:
    out spec P(model_axis, data_axes), so the assembled global table is the
    standard (m, B[, k]) prediction structure with owner s holding slots
    [s·spp, (s+1)·spp) — ``make_krr_predict_hashjoin`` consumes it without
    ever gathering it to one shard).

    The routing is derived from the slot-blocked layout's per-instance slot
    sort (owner grouping and per-bucket dedup fall out of the sorted order —
    no second sort; `tests/test_blocked_split.py` pins the op count).  Per
    CG iteration the apply is ONE route-pack (flat scatter-add through the
    precomputed point->cell map — or the Pallas route-pack kernel on that
    backend), two all_to_alls, an s-gather cross-run serve (the owner table
    is never materialized inside the loop; see ``_hashjoin_matvec``), and
    ONE route-unpack — the old vmap'd per-bucket segment_sum and the three
    intermediate scatter/gather hops are gone.

    ``y`` may be (n,) or an (n, k) RHS block: the k columns ride
    (cells, k) all_to_all payloads, so one routing build and two
    collectives per iteration amortize over all columns (PR 3's multi-RHS
    contract).  ``cfg.precond='jacobi'`` is supported — the diagonal is a
    model-axis psum and the apply shard-local, adding no per-iteration
    collectives; 'nystrom' still raises (its pivot columns need global
    matvecs).  The wire payload defaults to bfloat16 (accuracy pinned by
    tests); pass ``payload_dtype=jnp.float32`` for exact psum parity.  The
    final prediction table is always built with an f32 wire — it is one
    extra exchange per solve and serves every future prediction.

    The fourth output is a ``StepStats`` (replicated int32 counters):
    distinct buckets dropped past the routing capacity, plus non-finite
    wire cells zeroed in the final table exchange.  Feed it to
    ``check_step_stats`` (or use ``run_krr_step_resilient``) to enforce
    ``cfg.overflow``; ``cfg.fault_plan`` (tests) injects wire faults and
    shard stalls into the compiled step.
    """
    if cfg.precond == "nystrom":
        raise ValueError(
            "precond='nystrom' needs global matvecs for its pivot columns; "
            "the hash-join step supports 'jacobi' (shard-local apply)")
    if cfg.precond not in ("none", None, "jacobi"):
        raise ValueError(f"unknown preconditioner {cfg.precond!r}; "
                         f"expected one of {PRECOND_NAMES}")
    n_shards = _data_shard_count(mesh, cfg)
    if cfg.table_size % n_shards:
        raise ValueError("hash-join needs table_size divisible by the data "
                         f"shard count ({cfg.table_size} % {n_shards})")
    backend = resolve_backend(cfg.backend, platform_of(mesh))
    use_kernels = backend == "pallas"
    data_spec = P(cfg.data_axes)
    in_specs = (P(cfg.data_axes, None), data_spec,
                LSHParams(w=P(cfg.model_axis, None), z=P(cfg.model_axis, None),
                          r1=P(cfg.model_axis, None), r2=P(cfg.model_axis, None)))
    all_axes = tuple(cfg.data_axes) + (cfg.model_axis,)
    out_specs = (data_spec, P(), P(cfg.model_axis, cfg.data_axes),
                 StepStats(P(), P()))

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def step(x_local, y_local, lsh_local):
        maybe_stall(cfg.fault_plan, cfg.data_axes)
        op = _shard_operator(cfg, f, lsh_local, mesh)
        # blocked=True rides the layout's stable slot sort — the ONLY sort
        # in the step; parts='both' adds the route-kernel arrays on pallas
        idx = op.build_index(op.featurize(x_local), blocked=True,
                             parts=_hashjoin_layout_parts(backend))
        lay = idx.blocked
        m_loc = idx.slot.shape[0]
        rt = _build_routing(idx.slot, lay, n_shards, cfg.table_size,
                            cfg.data_axes, cap_factor, kernels=use_kernels)
        # routing geometry is jit-static (rt.cap is a Python int), so the
        # per-iteration all_to_all payload size is known at TRACE time —
        # recorded once per compilation, zero cost inside the loop
        k_cols = 1 if y_local.ndim == 1 else y_local.shape[1]
        obs.gauge(
            "hashjoin_a2a_payload_bytes",
            "per-shard all_to_all payload bytes per CG iteration "
            "(route + serve exchanges)").set(
            2 * n_shards * rt.cap * k_cols
            * jnp.dtype(payload_dtype).itemsize)
        mv = lambda v: _hashjoin_matvec(rt, lay, idx.coeff, cfg.m,
                                        cfg.data_axes, cfg.model_axis, v,
                                        payload_dtype, op.interpret,
                                        cfg.fault_plan)
        pre = _shard_preconditioner(cfg, None, idx)
        beta_local, resnorm = cg_iterations(mv, y_local, cfg,
                                            precond_apply=pre)
        # final sharded prediction table for the solved beta (f32 wire)
        table, wire_nf = _hashjoin_loads(rt, lay, idx.coeff, beta_local,
                                         cfg.data_axes, m_loc, jnp.float32,
                                         op.interpret, cfg.fault_plan)
        stats = StepStats(
            overflow_dropped=jax.lax.psum(rt.dropped, all_axes),
            wire_nonfinite=jax.lax.psum(wire_nf, all_axes))
        return beta_local, resnorm, table.reshape(
            (m_loc, rt.spp) + table.shape[1:]), stats

    return step


def _axes_linear_index(axes) -> Array:
    """This shard's linear index along (possibly multiple) mesh axes —
    row-major over ``axes``, matching all_to_all's shard order."""
    if isinstance(axes, str):
        return jax.lax.axis_index(axes)
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def _broadcast_readout(slot: Array, coeff: Array, table_flat: Array,
                       n_shards: int, spp: int, data_axes, model_axis,
                       m_total: int, payload_dtype) -> Array:
    """Route→serve→readout WITHOUT the dedup pack: every owner receives the
    raw (m_loc, n_loc) slot requests (one int32 all_to_all), serves the ones
    it owns (out-of-range ids gather 0 — each request has exactly ONE
    owner), and the value exchange sums over the owner axis.  No layout
    sort, no routing scatters, no capacity — nothing can overflow.  Wire is
    O(n_shards · m_loc · n_loc) instead of O(distinct cells): the tradeoff
    the SERVING tier wants at interactive batch sizes, where routing-build
    latency dominates the saved bytes (see make_krr_predict_hashjoin's
    ``dedup``).  Non-finite served values are sanitized to dropped mass,
    matching ``_hashjoin_readout``."""
    m_loc, n_loc = slot.shape
    send = jnp.broadcast_to(slot[None], (n_shards, m_loc, n_loc))
    recv = jax.lax.all_to_all(send, data_axes, 0, 0, tiled=True)
    local = recv - _axes_linear_index(data_axes) * spp
    ids = jnp.where((local >= 0) & (local < spp),
                    jnp.arange(m_loc, dtype=jnp.int32)[None, :, None] * spp
                    + local, m_loc * spp)
    served = table_flat.at[ids].get(mode="fill", fill_value=0)
    back = jax.lax.all_to_all(served.astype(payload_dtype), data_axes, 0, 0,
                              tiled=True).astype(jnp.float32)
    back = jnp.where(jnp.isfinite(back), back, 0.0)
    vals = jnp.sum(back, axis=0)                       # (m_loc, n_loc[, k])
    contrib = coeff[:, :, None] * vals if vals.ndim == 3 else coeff * vals
    return jax.lax.psum(jnp.sum(contrib, axis=0), model_axis) / m_total


def make_krr_predict_hashjoin(mesh: Mesh, cfg: KRRStepConfig, f: BucketFn, *,
                              cap_factor: float = 2.0,
                              payload_dtype=jnp.bfloat16,
                              with_stats: bool = False,
                              dedup: bool = True):
    """predict(x_test, lsh, table) -> yhat against a DATA-SHARDED table.

    ``table`` is the (m, B[, k]) structure assembled from
    ``make_krr_step_hashjoin``'s third output (spec
    P(model_axis, data_axes): shard s owns slots [s·spp, (s+1)·spp)).  Test
    points are data-sharded; each shard routes its points' slot requests to
    the owner shards, the owners serve their slices, and one value exchange
    assembles the predictions — the table the step already left sharded is
    consumable without a gather.  Returns (n_test,) or (n_test, k)
    predictions sharded P(data_axes).

    ``dedup=True`` (default — bulk scoring) packs DEDUPLICATED
    (instance, slot) cells through the training routing's slot-sorted
    layout: minimal wire bytes, amortized over large n.  ``dedup=False``
    (the serving tier's interactive mode) routes the raw requests instead —
    no layout sort, no routing scatters, no capacity to overflow — which at
    small padded batches is several times lower latency for strictly more
    wire bytes; the two modes agree bitwise on the reference backend (same
    table values, same coeff reduce, same psum).

    ``with_stats`` additionally returns a (data_shards,) int32 vector of
    distinct buckets dropped past the routing capacity PER SENDING DATA
    SHARD (summed over the model axis) — the serving tier folds this into
    ``health()`` so overflow under a hot query distribution is observable
    per shard instead of one global scalar.  (Always zero for
    ``dedup=False``: the broadcast route has no capacity.)"""
    n_shards = _data_shard_count(mesh, cfg)
    if cfg.table_size % n_shards:
        raise ValueError("hash-join needs table_size divisible by the data "
                         f"shard count ({cfg.table_size} % {n_shards})")
    backend = resolve_backend(cfg.backend, platform_of(mesh))
    use_kernels = backend == "pallas"
    in_specs = (P(cfg.data_axes, None),
                LSHParams(w=P(cfg.model_axis, None), z=P(cfg.model_axis, None),
                          r1=P(cfg.model_axis, None), r2=P(cfg.model_axis, None)),
                P(cfg.model_axis, cfg.data_axes))
    out_specs = ((P(cfg.data_axes), P(cfg.data_axes)) if with_stats
                 else P(cfg.data_axes))

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def predict(x_local, lsh_local, table_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        # flatten my (m_loc, spp[, k]) slice to the served id space
        table_flat = table_local.reshape((-1,) + table_local.shape[2:])
        if not dedup:
            idx = op.build_index(op.featurize(x_local), blocked=False)
            out = _broadcast_readout(idx.slot, idx.coeff, table_flat,
                                     n_shards, cfg.table_size // n_shards,
                                     cfg.data_axes, cfg.model_axis, cfg.m,
                                     payload_dtype)
            if with_stats:
                return out, jnp.zeros((1,), jnp.int32)
            return out
        idx = op.build_index(op.featurize(x_local), blocked=True,
                             parts=_hashjoin_layout_parts(backend))
        rt = _build_routing(idx.slot, idx.blocked, n_shards, cfg.table_size,
                            cfg.data_axes, cap_factor, kernels=use_kernels)
        out = _hashjoin_readout(rt, idx.blocked, idx.coeff, table_flat,
                                cfg.data_axes, cfg.model_axis, cfg.m,
                                payload_dtype, op.interpret,
                                plan=cfg.fault_plan)
        if with_stats:
            # dropped is per (model, data) shard; the model psum leaves one
            # replicated count per data shard -> P(data_axes) over (1,)
            # assembles the global (data_shards,) vector
            return out, jax.lax.psum(rt.dropped, cfg.model_axis)[None]
        return out

    return predict


def query_shard_touch(slots, table_size: int, n_shards: int):
    """(n, m) per-query table slots -> (n, n_shards) bool touch masks.

    Shard j owns slots [j·spp, (j+1)·spp) (spp = table_size / n_shards, the
    hash-join layout above), so a query's prediction depends ONLY on the
    shards its m slots land in.  The serving cache keys fold in exactly this
    touch set (+ per-shard piece versions): reloading one shard's table
    piece then invalidates only the entries whose slots touch it.  Pure
    numpy — the cache path must never enter the jit runtime."""
    slots = np.asarray(slots)
    if table_size % n_shards:
        raise ValueError(f"table_size={table_size} not divisible by "
                         f"n_shards={n_shards}")
    owners = slots // (table_size // n_shards)
    touch = np.zeros((slots.shape[0], n_shards), bool)
    touch[np.arange(slots.shape[0])[:, None], owners] = True
    return touch
