"""Compile every Pallas kernel of kernels/featurize and kernels/binning, and
the serving program around them, for a TPU v5e, at the width of the paper's
Forest Cover fit (d=54, n=500,000, m=64, B=2^21), with ``interpret=False``.

Nothing runs: the TPU compiler is asked about a described v5e chip, so these
tests catch what the Pallas interpreter cannot see — block shapes the chip's
tiling refuses, unsupported ops, scalar memory over its size.  Each test
also checks that the compiled program holds a Mosaic kernel
(``tpu_custom_call``), so an interpret-mode lowering cannot pass for one;
the kernel tests, that it carries its stable name (its op name on a device
trace).

The topology is described inside a module fixture (never at import time):
only the worker that runs this file loads the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import hlo_analysis
from repro.core import get_bucket_fn, make_operator, sample_lsh_params
from repro.core.krr import WLSHKRRModel
from repro.core.lsh import GammaPDF
from repro.core.operator import default_table_size
from repro.core.wlsh import BlockedLayout, TableIndex
from repro.kernels.binning import (bin_fused_matvec_op,
                                   bin_fused_matvec_pallas,
                                   bin_gather_blocked_pallas,
                                   bin_scatter_blocked_pallas,
                                   route_pack_pallas, route_unpack_pallas)
from repro.kernels.featurize import featurize_pallas
from repro.serve import Predictor
from repro.serve.artifact import LoadedArtifact

M, N, D, B = 64, 500_000, 54, 1 << 21       # Forest Cover fit, m=64
BN, BT = 128, 512                            # slot-blocked layout geometry
TILES = B // BT
NB = N // BN + TILES                         # layout blocks per instance
NS = -(-N // BN) * BN                        # sorted order, whole blocks
N_LOC = N // 4                               # hash-join: 4 data shards
NB_LOC = N_LOC // BN + TILES
CELL_TILES = M * N_LOC // BT                 # wire cells / tile width
VB = NB_LOC + CELL_TILES                     # unpack visits per instance


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compile_tpu(one_chip, no_cache):
    def run(fn, *shapes, **static):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        text = jax.jit(functools.partial(fn, interpret=False, **static)) \
            .lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return run


I32, U32, F32 = jnp.int32, jnp.uint32, jnp.float32


@pytest.mark.parametrize("bucket", ["rect", "smooth"])
def test_featurize_compiles(compile_tpu, bucket):
    n_pad = -(-N // 1024) * 1024
    text = compile_tpu(featurize_pallas, ((n_pad, D), F32), ((M, D), F32),
                       ((M, D), F32), ((M, D), U32), ((M, D), U32),
                       f=get_bucket_fn(bucket))
    assert "%wlsh_featurize" in text


@pytest.mark.parametrize("k", [None, 4])
def test_fused_matvec_compiles(compile_tpu, k):
    lay = (M, NB * BN)
    beta = (M, NS) if k is None else (M, k, NS)
    text = compile_tpu(bin_fused_matvec_pallas, ((M, 2 * NB), I32),
                       ((M, 2 * NB), I32), ((M, 2 * NB), I32), (lay, I32),
                       (lay, F32), (beta, F32), block_n=BN, block_t=BT)
    assert "%wlsh_fused_matvec" in text


@pytest.mark.parametrize("n, fits", [(1 << 20, True), ((1 << 20) + 1, False)])
def test_fused_matvec_smem_boundary(compile_tpu, n, fits):
    """One instance's fused schedule (three lists of 2·NB int32 visits:
    layout block, tile and phase packed as 2·tile + phase, and the sorted
    beta offset; NB = n/bn + B/bt) fits the SMEM budget of one call up to
    n = 2^20 at B = default_table_size(n); one point more doubles B and is
    refused."""
    nb = n // BN + default_table_size(n) // BT
    shapes = 3 * [((1, 2 * nb), I32)] + [((1, nb * BN), I32),
                                         ((1, nb * BN), F32),
                                         ((1, -(-n // BN) * BN), F32)]
    if fits:
        compile_tpu(bin_fused_matvec_pallas, *shapes, block_n=BN, block_t=BT)
    else:
        with pytest.raises(ValueError, match="SMEM"):
            compile_tpu(bin_fused_matvec_pallas, *shapes, block_n=BN,
                        block_t=BT)


@pytest.mark.parametrize("k", [None, 4])
def test_fused_matvec_gathers_sorted_order(compile_tpu, k):
    """The fused matvec op at the Forest shape reads beta in each
    instance's sorted order: no gather in the compiled program yields more
    than m·n values per RHS column.  Gathering beta along the padded layout
    yielded m·L = m·(n/bn + B/bt)·bn, here over twice as many."""
    def matvec(slot, order, inv_pos, slot_lay, coeff_lay, v_block,
               v_tile_phase, v_off, beta, *, interpret):
        lay = BlockedLayout(**{
            **dict.fromkeys(BlockedLayout._fields),
            **dict(order=order, inv_pos=inv_pos, slot_lay=slot_lay,
                   coeff_lay=coeff_lay, v_block=v_block,
                   v_tile_phase=v_tile_phase, v_off=v_off, block_n=BN,
                   block_t=BT, num_tiles=TILES)})
        index = TableIndex(slot=slot, sign=None, weight=None, coeff=None,
                           table_size=B, blocked=lay)
        return bin_fused_matvec_op(index, beta, interpret=interpret)

    points, lay, visits = (M, N), (M, NB * BN), (M, 2 * NB)
    beta = (N,) if k is None else (N, k)
    text = compile_tpu(matvec, (points, I32), (points, I32), (points, I32),
                       (lay, I32), (lay, F32), (visits, I32), (visits, I32),
                       (visits, I32), (beta, F32))
    assert "%wlsh_fused_matvec" in text
    gathered = [int(np.prod([int(d) for d in dims.split(",")]))
                for dims in re.findall(r"= f32\[([\d,]+)\]\S* gather\(",
                                       text)]
    assert gathered and max(gathered) <= M * N * (k or 1)
    assert M * NB * BN > 2 * M * N


@pytest.mark.parametrize("k", [None, 4])
def test_blocked_scatter_compiles(compile_tpu, k):
    lay = (M, NB * BN)
    contrib = lay if k is None else (M, k, NB * BN)
    text = compile_tpu(bin_scatter_blocked_pallas, ((M, NB), I32),
                       ((M, NB), I32), (lay, I32), (contrib, F32),
                       num_tiles=TILES, block_n=BN, block_t=BT)
    assert "%wlsh_blocked_scatter" in text


@pytest.mark.parametrize("k", [None, 4])
def test_blocked_gather_compiles(compile_tpu, k):
    tables = (M, B) if k is None else (M, k, B)
    text = compile_tpu(bin_gather_blocked_pallas, ((M, NB), I32),
                       ((M, NB * BN), I32), (tables, F32), block_n=BN,
                       block_t=BT)
    assert "%wlsh_blocked_gather" in text


def test_serve_readout_is_a_row_gather(topo, one_chip, no_cache):
    """The Predictor's jitted featurize -> readout at the Forest serving
    shape (a padding bucket of 16 queries): the index carries no layout, so
    the loads are a row gather of the (m, B) table parameter.  No
    cross-product kernel, and no op but the parameter holds a table-sized
    buffer (the cross-product path copied it to (m, 1, B))."""
    lsh = sample_lsh_params(jax.random.PRNGKey(0), M, D, GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), B,
                       platform=topo.devices[0].platform)
    assert (op.backend, op.interpret) == ("pallas", False)
    model = WLSHKRRModel(lsh=lsh, bucket_name="rect",
                         beta=jnp.zeros((0,), F32), tables=None,
                         table_size=B, cg_iters=jnp.asarray(0),
                         cg_resnorm=jnp.asarray(0.0), backend="pallas")
    predictor = Predictor()
    aid = predictor.add_model(LoadedArtifact(
        artifact_id="forest", model=model, operator=op, norm=None, meta={}))
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((M, B), (16, D))]
    text = predictor._hosted(aid).predict_fn.lower(*args).compile().as_text()
    assert "%wlsh_featurize" in text
    assert "wlsh_readout_gather" not in text
    big = {s for s in hlo_analysis.tensor_shapes(text)
           if np.prod(s[1]) >= M * B}
    assert big == {("f32", (M, B))}
    table_ops = re.findall(rf"= f32\[{M},{B}\]\S* (\S+)\(", text)
    assert table_ops and set(table_ops) == {"parameter"}


def test_route_pack_compiles(compile_tpu):
    v = CELL_TILES + M * VB
    text = compile_tpu(route_pack_pallas, ((v,), I32), ((v,), I32),
                       ((v,), I32), ((v,), I32), ((M, NB_LOC * BN), I32),
                       ((M, NB_LOC * BN), F32), num_cell_tiles=CELL_TILES,
                       block_n=BN, block_t=BT)
    assert "%wlsh_route_pack" in text


def test_route_unpack_compiles(compile_tpu):
    text = compile_tpu(route_unpack_pallas, ((M, VB), I32), ((M, VB), I32),
                       ((M, VB), I32), ((M, NB_LOC * BN), I32),
                       ((M, NB_LOC * BN), F32), ((1, CELL_TILES * BT), F32),
                       block_n=BN, block_t=BT)
    assert "%wlsh_route_unpack" in text


def test_placement_on_tpu_picks_compiled_kernels(topo):
    """Backend and interpret mode follow the devices a program is placed on:
    a mesh of TPU devices gets the compiled pallas kernels even from a CPU
    host, and asking for the interpreter there is refused."""
    from repro.core.distributed import KRRStepConfig, _shard_operator
    lsh = sample_lsh_params(jax.random.PRNGKey(0), 4, 3, GammaPDF(2.0, 1.0))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    cfg = KRRStepConfig(m=4, table_size=512, lam=0.5, cg_iters=1)
    op = _shard_operator(cfg, get_bucket_fn("rect"), lsh, mesh)
    assert (op.backend, op.interpret) == ("pallas", False)
    op = make_operator(lsh, get_bucket_fn("rect"), 512,
                       platform=topo.devices[0].platform)
    assert (op.backend, op.interpret) == ("pallas", False)
    with pytest.raises(ValueError, match="interpret"):
        make_operator(lsh, get_bucket_fn("rect"), 512, backend="pallas",
                      interpret=True, platform=topo.devices[0].platform)
