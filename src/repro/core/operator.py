"""The WLSH operator — one spine for every execution path (DESIGN.md §3).

``WLSHOperator`` bundles the m LSH instances, the bucket-shaping function and
the CountSketch table geometry behind a small primitive set:

    featurize       points -> Features            (hash + weight + sign)
    build_index     Features -> Table/Exact index (per-point-set structure)
    loads           index, beta -> (m, B) tables  (CountSketch scatter)
    readout         index, tables -> per-point    (CountSketch gather)
    matvec          index, beta -> K~ beta        (fused one-pass when the
                    index carries the slot-blocked layout, else
                    readout ∘ loads)
    featurize_buckets    x_query -> TableIndex    (query hash half of predict)
    predict_from_buckets index, tables -> yhat    (readout half of predict —
                         pure function of the query's bucket structure)
    predict_batched      tables, x_test -> yhat   (streaming, fixed memory;
                         wrapper over the two halves)

Every primitive dispatches on ``backend``:

* ``reference`` — the pure-jnp path (core/lsh.py + core/wlsh.py).
* ``pallas``    — the fused kernels (kernels/featurize + kernels/binning),
  compiled on a TPU and interpreted on the CPU, with all shape padding
  handled internally.
* ``auto``      — resolved per platform at construction (see repro.backend).

The platform is the one the program is placed on: ``make_operator`` takes
it from the caller (the device of the data, or a mesh's devices).

The solver (core/krr.py), the distributed step (core/distributed.py) and the
benchmarks all talk to this interface only, so swapping kernels or meshes is
a one-file change.  The distributed path constructs an operator *inside*
shard_map from its local LSH shard: ``loads`` then produces local partial
tables (psum-able across data shards) and ``readout(average=False)`` the
local instance-sum (psum-able across the model axis).
"""
from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

from ..backend import platform_of, resolve_backend, resolve_interpret
from .bucket_fns import BucketFn
from .lsh import Features, LSHParams, featurize as featurize_reference
from .wlsh import (ExactIndex, TableIndex, build_blocked_layout,
                   build_exact_index, build_table_index, exact_matvec,
                   table_loads, table_matvec_fused, table_readout)

Array = jnp.ndarray
Index = Union[TableIndex, ExactIndex]


def default_table_size(n: int, *, min_pow: int = 8) -> int:
    """CountSketch table-size heuristic: the smallest power of two >= 4n
    (>= 2^min_pow) keeps same-slot collisions rare."""
    return 1 << max(min_pow, int(4 * max(n, 1) - 1).bit_length())


class WLSHOperator(NamedTuple):
    """Backend-dispatched WLSH primitive set bound to m LSH instances.

    A NamedTuple so it can be built inside jit/shard_map from traced local
    LSH shards and closed over freely; ``backend`` must already be concrete
    ('reference' or 'pallas') — use ``make_operator`` to resolve 'auto' and
    to choose ``interpret`` for the platform.
    """

    lsh: LSHParams
    bucket: BucketFn
    table_size: int
    backend: str = "reference"
    interpret: bool = False      # Pallas interpreter, CPU only (ignored by
                                 # reference)

    # -- featurization ------------------------------------------------------

    def featurize(self, x: Array) -> Features:
        if self.backend == "pallas":
            from ..kernels.featurize import featurize_op
            return featurize_op(self.lsh, self.bucket, x,
                                interpret=self.interpret)
        return featurize_reference(self.lsh, self.bucket, x)

    # -- index construction -------------------------------------------------

    def build_index(self, feats: Features, mode: str = "table", *,
                    blocked: bool = True,
                    parts: str | None = None) -> Index:
        """'table' -> CountSketch TableIndex (both backends); 'exact' ->
        sorted-bucket ExactIndex (reference-only validation path).

        ``blocked`` attaches the slot-blocked layout (one-off per-instance
        sort + per-tile offsets) consumed by the fused matvec AND by the
        pallas split scatter/gather (``loads``/``readout`` dispatch to the
        visit-list kernels when the layout is present — the distributed
        psum path schedules only real collisions while keeping the
        (m, B[, k]) tables in HBM).  Readout-only consumers (prediction)
        pass ``blocked=False`` to skip the sort.  ``parts`` overrides which
        layout array group is materialized (default: this backend's own) —
        the hash-join step passes 'both' on the pallas backend because its
        routing build consumes the reference group (perm/segments) while
        its route kernels consume the pallas group (src/coeff_lay).
        """
        if mode == "table":
            idx = build_table_index(feats, self.table_size)
            if blocked:
                # only materialize the array group this backend's fused
                # matvec consumes (the groups are disjoint and O(mn)-sized)
                idx = idx._replace(blocked=build_blocked_layout(
                    idx.slot, idx.coeff, self.table_size,
                    parts=self.backend if parts is None else parts))
            return idx
        if mode == "exact":
            return build_exact_index(feats)
        raise ValueError(f"unknown mode {mode!r}")

    # -- CountSketch scatter / gather ---------------------------------------

    def loads(self, index: TableIndex, beta: Array) -> Array:
        """Bucket-load tables for beta — the psum-able object.  (m, B) for a
        (n,) beta; (m, B, k) for a (n, k) RHS block (columns independent).
        On the pallas backend an index carrying the slot-blocked layout
        scatters through the visit-list kernel (O(n/bn + B/bt) grid); any
        other index through ``table_loads`` — same tables, same psum."""
        if self.backend == "pallas":
            from ..kernels.binning import bin_loads_op
            return bin_loads_op(index, beta, interpret=self.interpret)
        return table_loads(index, beta)

    def readout(self, index: TableIndex, tables: Array, *,
                average: bool = True) -> Array:
        """Per-point readout of (possibly psum-merged) tables.  ``average``
        gives (1/m) sum_s; ``average=False`` gives the plain instance sum
        (the distributed path divides by the global m after its psum)."""
        if self.backend == "pallas":
            from ..kernels.binning import bin_readout_op
            return bin_readout_op(index, tables, average=average,
                                  interpret=self.interpret)
        return table_readout(index, tables, average=average)

    # -- matvec -------------------------------------------------------------

    def matvec(self, index: Index, beta: Array, *,
               average: bool = True) -> Array:
        """K~ beta in O(n m); ``beta`` is (n,) or an (n, k) RHS block.

        The k columns of a block share the index, the slot sort and (on the
        fused paths) every one-hot tile product / segment id — a block-CG
        solve or batched GP-posterior fit costs far less than k single
        solves (see core/krr.py:pcg_solve).

        Table mode dispatches on the index alone: when it carries this
        backend's slot-blocked layout group the scatter and gather run in
        one pass — a single Pallas kernel whose table tile stays in VMEM, or
        the reference sorted segment-sum — so the (m, B) table is never
        materialized between them.  Otherwise it runs the split
        readout ∘ loads composition.  A caller that must merge the tables
        between the two halves (the data-sharded psum in core/distributed)
        calls ``loads`` and ``readout`` itself.  Exact mode is the reference
        sorted-bucket estimator (``average`` only).
        """
        if isinstance(index, ExactIndex):
            if not average:
                raise ValueError("exact-mode matvec only supports average=True")
            return exact_matvec(index, beta)
        lay = index.blocked
        if lay is not None:
            # each backend consumes its own layout group; an index built by
            # the other backend degrades to the split path below
            if self.backend == "pallas" and lay.order is not None:
                from ..kernels.binning import bin_fused_matvec_op
                return bin_fused_matvec_op(index, beta, average=average,
                                           interpret=self.interpret)
            if self.backend != "pallas" and lay.perm is not None:
                return table_matvec_fused(index, beta, average=average)
        return self.readout(index, self.loads(index, beta), average=average)

    # -- streaming prediction -----------------------------------------------

    def featurize_buckets(self, x: Array) -> TableIndex:
        """Query half of the prediction path: featurize ``x`` and build the
        readout-only table index (no slot-blocked layout — prediction never
        scatters).  The result is the per-query bucket structure: its
        (slot, coeff) pairs are everything a prediction depends on, which is
        what makes bucket-keyed caching exact (serve/cache.py) and lets the
        serving layer split the query hash from the table gather."""
        return self.build_index(self.featurize(x), blocked=False)

    def predict_from_buckets(self, index: TableIndex, tables: Array) -> Array:
        """Readout half of the prediction path: predictions for an already
        bucketed query set.  Pure function of (index.slot, index.coeff) and
        ``tables`` — no access to the raw points.  Tables may be (m, B) ->
        (n_query,) predictions, or (m, B, k) -> (n_query, k)."""
        return self.readout(index, tables)

    def predict_batched(self, tables: Array, x_test: Array, *,
                        batch_size: int | None = None) -> Array:
        """Read test-point predictions out of prebuilt bucket-load tables —
        a thin wrapper over ``featurize_buckets`` + ``predict_from_buckets``.

        With ``batch_size`` the test set is processed in fixed-size blocks via
        ``lax.map`` — peak memory is O(batch_size * m) regardless of n_test,
        which is what lets multi-million-point inference stream.  Tables may
        be (m, B) -> (n_test,) predictions, or (m, B, k) -> (n_test, k) (one
        streamed readout serves all k fitted columns)."""
        n = x_test.shape[0]
        if batch_size is None or batch_size >= n:
            return self.predict_from_buckets(self.featurize_buckets(x_test),
                                             tables)
        n_blocks = -(-n // batch_size)
        xp = jnp.pad(jnp.asarray(x_test, jnp.float32),
                     ((0, n_blocks * batch_size - n), (0, 0)))
        blocks = xp.reshape(n_blocks, batch_size, x_test.shape[1])

        def one_block(xb):
            return self.predict_from_buckets(self.featurize_buckets(xb),
                                             tables)

        out = jax.lax.map(one_block, blocks)
        return out.reshape((-1,) + out.shape[2:])[:n]


def make_operator(lsh: LSHParams, bucket: BucketFn, table_size: int, *,
                  backend: str | None = "auto",
                  interpret: bool | None = None,
                  platform: str | None = None) -> WLSHOperator:
    """Construct an operator with 'auto' backend and interpret mode resolved
    for ``platform`` — the platform the program is placed on (default: the
    default device's).  Everything downstream sees a concrete backend.
    ``interpret=None`` interprets the kernels exactly off the TPU; asking
    for the interpreter on a TPU raises.  Which table-matvec path runs is
    the index's to say (``build_index(blocked=...)``), not the operator's."""
    platform = platform_of() if platform is None else platform
    return WLSHOperator(lsh=lsh, bucket=bucket, table_size=int(table_size),
                        backend=resolve_backend(backend, platform),
                        interpret=resolve_interpret(interpret, platform))
