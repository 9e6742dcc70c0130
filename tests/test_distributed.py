"""Distributed KRR vs single-device reference.

Two tiers:

* **in-process** — the tests below run directly whenever the pytest process
  already sees >= 2 devices (the CI ``multidevice`` job sets
  ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` before pytest
  starts), so the sharded psum/collective paths are exercised for real, not
  only under subprocess mocks.  With one device they skip.
* **subprocess** (slow tier) — 8 fake CPU devices spawned per test (the
  flag must be set before jax initializes, which pytest's main process has
  already done when it only sees one device).
"""
import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

needs_multi = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (CI multidevice job sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=2)")

needs_4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (CI multidevice job sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _mesh_2shard():
    from repro.compat import make_mesh
    return make_mesh((1, 2, 1), ("pod", "data", "model"))


def _problem(n=256, d=4, m=4, table_size=1024):
    from repro.core import GammaPDF, featurize, get_bucket_fn, \
        sample_lsh_params
    from repro.core.wlsh import build_table_index
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (n, d)) * 2.0
    beta = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    lsh = sample_lsh_params(jax.random.fold_in(key, 2), m, d,
                            GammaPDF(2.0, 1.0))
    f = get_bucket_fn("rect")
    idx = build_table_index(featurize(lsh, f, x), table_size)
    return x, beta, lsh, f, idx


@needs_multi
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_psum_matvec_2shards_matches_single_device(backend):
    """Satellite acceptance: a 2-shard CPU-mesh psum matvec matches the
    single-device split matvec <= 1e-6 — on the pallas backend through the
    blocked visit-list split kernels (the index carries the layout)."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core.distributed import (KRRStepConfig,
                                        _shard_operator,
                                        make_distributed_matvec)
    from repro.core.lsh import LSHParams
    from repro.core.wlsh import table_matvec
    n, m, table_size = 256, 4, 1024
    x, beta, lsh, f, idx = _problem(n=n, m=m, table_size=table_size)
    mesh = _mesh_2shard()
    cfg = KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=5,
                        data_axes=("pod", "data"), model_axis="model",
                        backend=backend)
    lsh_specs = LSHParams(w=P("model", None), z=P("model", None),
                          r1=P("model", None), r2=P("model", None))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(("pod", "data"), None), P(("pod", "data")), lsh_specs),
        out_specs=P(("pod", "data")))
    def mv(x_local, beta_local, lsh_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        i = op.build_index(op.featurize(x_local),
                           blocked=backend == "pallas")
        return make_distributed_matvec(cfg, op, n_data_shards=2)(
            i, beta_local)

    got = jax.jit(mv)(x, beta, lsh)
    want = table_matvec(idx, beta)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6


@needs_multi
def test_krr_step_2shards_blocked_split_matches_reference():
    """The 2-shard pallas step (blocked split kernels around the table
    psum) agrees with the reference-backend step.  Converged solves
    (cg_iters=50, resnorm ~1e-7) — a fixed-iteration CG amplifies ulp-level
    matvec differences to residual scale before convergence, so mid-solve
    betas are not comparable."""
    from repro.core.distributed import KRRStepConfig, make_krr_step
    n, m, table_size = 256, 4, 1024
    x, _, lsh, f, _ = _problem(n=n, m=m, table_size=table_size)
    y = jax.random.normal(jax.random.PRNGKey(3), (n,))
    mesh = _mesh_2shard()
    base = KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=50,
                         data_axes=("pod", "data"), model_axis="model",
                         backend="pallas")
    b_blk, _, t_blk = jax.jit(make_krr_step(mesh, base, f))(x, y, lsh)
    b_ref, _, t_ref = jax.jit(make_krr_step(
        mesh, base._replace(backend="reference"), f))(x, y, lsh)
    np.testing.assert_allclose(np.asarray(b_blk), np.asarray(b_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(t_blk), np.asarray(t_ref),
                               atol=1e-4)


@needs_multi
def test_psum_matvec_2shards_multi_rhs():
    """An (n, k) RHS block through the 2-shard psum sandwich (blocked split
    kernels) matches k single-device matvec columns <= 1e-6."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core.distributed import (KRRStepConfig,
                                        _shard_operator,
                                        make_distributed_matvec)
    from repro.core.lsh import LSHParams
    from repro.core.wlsh import table_matvec
    n, m, table_size, k = 256, 4, 1024, 3
    x, _, lsh, f, idx = _problem(n=n, m=m, table_size=table_size)
    bk = jax.random.normal(jax.random.PRNGKey(5), (n, k))
    mesh = _mesh_2shard()
    cfg = KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=5,
                        data_axes=("pod", "data"), model_axis="model",
                        backend="pallas")
    lsh_specs = LSHParams(w=P("model", None), z=P("model", None),
                          r1=P("model", None), r2=P("model", None))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(("pod", "data"), None), P(("pod", "data"), None),
                  lsh_specs),
        out_specs=P(("pod", "data"), None))
    def mv(x_local, bk_local, lsh_local):
        op = _shard_operator(cfg, f, lsh_local, mesh)
        i = op.build_index(op.featurize(x_local), blocked=True)
        return make_distributed_matvec(cfg, op, n_data_shards=2)(
            i, bk_local)

    got = jax.jit(mv)(x, bk, lsh)
    want = table_matvec(idx, bk)
    # k columns accumulate k× the summation-order noise of the 1e-6
    # single-RHS bound
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-6

_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import sample_lsh_params, GammaPDF, get_bucket_fn, featurize
from repro.core.wlsh import build_table_index, table_matvec
from repro.core.krr import cg_solve
from repro.core.distributed import KRRStepConfig, make_krr_step, make_krr_predict

assert len(jax.devices()) == 8
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
n, d, m, B = 256, 4, 8, 512
key = jax.random.PRNGKey(0)
x = jax.random.uniform(key, (n, d)) * 2.0
y = jax.random.normal(jax.random.PRNGKey(1), (n,))
lsh = sample_lsh_params(jax.random.PRNGKey(2), m, d, GammaPDF(2.0, 1.0))
f = get_bucket_fn("rect")
cfg = KRRStepConfig(m=m, table_size=B, lam=0.5, cg_iters=25,
                    data_axes=("pod", "data"), model_axis="model")
beta, resnorm, tables = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)

feats = featurize(lsh, f, x)
idx = build_table_index(feats, B)
ref = cg_solve(lambda v: table_matvec(idx, v), y, 0.5, tol=0.0, maxiter=25)
err = float(jnp.max(jnp.abs(jax.device_get(beta) - ref.x)))
assert err < 1e-3, f"beta mismatch {err}"

pred = jax.jit(make_krr_predict(mesh, cfg, f))(x, lsh, tables)
err2 = float(jnp.max(jnp.abs(pred - table_matvec(idx, ref.x))))
assert err2 < 1e-3, f"predict mismatch {err2}"
print("DISTRIBUTED_OK", err, err2)
"""


@pytest.mark.slow
def test_distributed_krr_matches_reference():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, cwd=".", timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DISTRIBUTED_OK" in proc.stdout


_DP_SCRIPT = r"""
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.optim import compressed_psum

assert len(jax.devices()) == 8
mesh = make_mesh((8,), ("pod",))
x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0

@partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))
def summed(v):
    local = v[0]
    return compressed_psum(local, "pod", jax.random.PRNGKey(0))[None]

out = summed(x)
exact = jnp.sum(x, axis=0)
err = float(jnp.max(jnp.abs(out[0] - exact)))
scale = float(jnp.max(jnp.abs(x))) / 127.0
assert err <= 8 * scale + 1e-6, (err, scale)
print("COMPRESSED_PSUM_OK", err)
"""


@pytest.mark.slow
def test_compressed_psum_across_8_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _DP_SCRIPT],
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, cwd=".", timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "COMPRESSED_PSUM_OK" in proc.stdout


_HJ_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import sample_lsh_params, GammaPDF, get_bucket_fn
from repro.core.distributed import (KRRStepConfig, make_krr_step,
                                    make_krr_step_hashjoin)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
n, d, m, B = 512, 5, 8, 1024
key = jax.random.PRNGKey(0)
x = jax.random.uniform(key, (n, d)) * 2.0
y = jax.random.normal(jax.random.PRNGKey(1), (n,))
lsh = sample_lsh_params(jax.random.PRNGKey(2), m, d, GammaPDF(2.0, 1.0))
f = get_bucket_fn("rect")
cfg = KRRStepConfig(m=m, table_size=B, lam=0.5, cg_iters=25,
                    data_axes=("pod", "data"), model_axis="model")
b1, r1, _ = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
b2, r2, _, _ = jax.jit(make_krr_step_hashjoin(mesh, cfg, f, cap_factor=8.0,
                                              payload_dtype=jnp.float32))(
    x, y, lsh)
err = float(jnp.max(jnp.abs(jax.device_get(b1) - jax.device_get(b2))))
assert err < 1e-4, f"hashjoin != psum: {err}"
# the default bf16 wire stays within the pinned accuracy band of the f32 run
b3, _, _, _ = jax.jit(make_krr_step_hashjoin(mesh, cfg, f, cap_factor=8.0))(
    x, y, lsh)
b2h, b3h = jax.device_get(b2), jax.device_get(b3)
rel = float(jnp.linalg.norm(b3h - b2h) / jnp.linalg.norm(b2h))
assert rel < 1e-2, f"bf16 wire drift {rel}"
print("HASHJOIN_OK", err, rel)
"""


def _hj_problem(n=192, d=3, m=4, table_size=512):
    from repro.core import GammaPDF, get_bucket_fn, sample_lsh_params
    key = jax.random.PRNGKey(6)
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    lsh = sample_lsh_params(jax.random.fold_in(key, 2), m, d,
                            GammaPDF(2.0, 1.0))
    return x, y, lsh, get_bucket_fn("rect")


def _hj_cfg(m=4, table_size=512, **kw):
    from repro.core.distributed import KRRStepConfig
    return KRRStepConfig(m=m, table_size=table_size, lam=0.5, cg_iters=15,
                         data_axes=("pod", "data"), model_axis="model",
                         backend="reference", **kw)


def _mesh_1():
    from repro.compat import make_mesh
    return make_mesh((1, 1, 1), ("pod", "data", "model"))


def test_hashjoin_bf16_wire_accuracy_pinned():
    """The default bfloat16 wire (f32 accumulate, one rounding per hop)
    stays within 1% relative L2 of the f32-wire solve — the pinned accuracy
    bound for halving the all_to_all bytes."""
    from repro.core.distributed import make_krr_step_hashjoin
    x, y, lsh, f = _hj_problem()
    mesh, cfg = _mesh_1(), _hj_cfg()
    b_f32, _, _, _ = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(x, y, lsh)
    b_bf16, _, _, _ = jax.jit(make_krr_step_hashjoin(mesh, cfg, f))(x, y,
                                                                    lsh)
    rel = float(jnp.linalg.norm(b_bf16 - b_f32) / jnp.linalg.norm(b_f32))
    assert rel < 1e-2, rel
    assert rel > 0.0          # the wire really is bf16, not silently f32


def test_hashjoin_capacity_overflow_drops_stay_finite():
    """A cap_factor far below 1 forces per-destination capacity overflow:
    excess buckets are DROPPED (sentinel-routed), never misrouted — the
    solve stays finite and in the neighborhood of the exact-table solve
    (the estimator loses mass but not stability)."""
    from repro.core.distributed import make_krr_step, make_krr_step_hashjoin
    x, y, lsh, f = _hj_problem()
    mesh, cfg = _mesh_1(), _hj_cfg()
    b_ps, _, _ = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    b_ov, res, _, stats = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, cap_factor=0.05, payload_dtype=jnp.float32))(x, y, lsh)
    assert bool(jnp.isfinite(b_ov).all())
    assert bool(jnp.isfinite(res).all())
    # the drops are ACCOUNTED, not silent: the same pack pass that routes
    # cells counts the ones past capacity
    assert int(stats.overflow_dropped) > 0
    rel = float(jnp.linalg.norm(b_ov - b_ps) / jnp.linalg.norm(b_ps))
    assert rel < 0.5, rel     # degraded, but still the same system


def test_hashjoin_overflow_counter_zero_at_ample_capacity():
    """At cap_factor=1.25 the per-destination capacity exceeds the max
    possible distinct cells per owner on this problem — the overflow counter
    must be EXACTLY zero (the accounting has no false positives)."""
    from repro.core.distributed import make_krr_step_hashjoin
    x, y, lsh, f = _hj_problem()
    mesh, cfg = _mesh_1(), _hj_cfg()
    _, _, _, stats = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, cap_factor=1.25, payload_dtype=jnp.float32))(x, y, lsh)
    assert int(stats.overflow_dropped) == 0
    assert int(stats.wire_nonfinite) == 0


def test_hashjoin_nan_wire_cell_detected_never_silent():
    """A NaN-poisoned wire cell must surface as a NaN resnorm sentinel (the
    CG loop propagates it into detection) — never as a silently-finite,
    silently-wrong beta next to an all-clean residual report."""
    from repro.core.distributed import make_krr_step_hashjoin
    from repro.testing import FaultPlan
    x, y, lsh, f = _hj_problem()
    mesh = _mesh_1()
    cfg = _hj_cfg(fault_plan=FaultPlan(wire_nan_frac=0.3, seed=7))
    b, res, _, stats = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(x, y, lsh)
    assert not bool(jnp.isfinite(res).all())   # sentinel fired
    assert int(stats.wire_nonfinite) > 0       # and the wire count saw it


def test_hashjoin_multi_rhs_matches_psum_block():
    """An (n, k) RHS block through the hash-join step matches the psum
    step's block solve: the k columns ride (cells, k) payloads — one
    routing build and two all_to_alls per iteration for all k."""
    from repro.core.distributed import make_krr_step, make_krr_step_hashjoin
    x, _, lsh, f = _hj_problem()
    yk = jax.random.normal(jax.random.PRNGKey(11), (x.shape[0], 3))
    mesh, cfg = _mesh_1(), _hj_cfg()
    bk_ps, _, t_ps = jax.jit(make_krr_step(mesh, cfg, f))(x, yk, lsh)
    bk_hj, _, t_hj, _ = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(x, yk, lsh)
    np.testing.assert_allclose(np.asarray(bk_hj), np.asarray(bk_ps),
                               atol=1e-5)
    assert t_hj.shape == (4, 512, 3)   # sharded table keeps the RHS axis


def test_hashjoin_jacobi_matches_psum_jacobi():
    """precond='jacobi' rides the hash-join step (diagonal via model psum,
    apply shard-local) and matches the psum step's PCG trajectory."""
    from repro.core.distributed import make_krr_step, make_krr_step_hashjoin
    x, y, lsh, f = _hj_problem()
    mesh, cfg = _mesh_1(), _hj_cfg(precond="jacobi")
    b_ps, _, _ = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    b_hj, _, _, _ = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(x, y, lsh)
    np.testing.assert_allclose(np.asarray(b_hj), np.asarray(b_ps), atol=1e-5)


def test_hashjoin_nystrom_rejected():
    from repro.core.distributed import make_krr_step_hashjoin
    with pytest.raises(ValueError, match="nystrom"):
        make_krr_step_hashjoin(_mesh_1(), _hj_cfg(precond="nystrom"),
                               _hj_problem()[3])


def test_hashjoin_predict_sharded_table_matches_psum_predict():
    """make_krr_predict_hashjoin consumes the step's data-SHARDED table
    (readout-half routing: slot requests to owner shards) and matches the
    psum predict on the replicated tables."""
    from repro.core.distributed import (make_krr_predict,
                                        make_krr_predict_hashjoin,
                                        make_krr_step,
                                        make_krr_step_hashjoin)
    x, y, lsh, f = _hj_problem()
    xt = jax.random.uniform(jax.random.PRNGKey(13), (64, x.shape[1])) * 2.0
    mesh, cfg = _mesh_1(), _hj_cfg()
    _, _, t_ps = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    _, _, t_hj, _ = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(x, y, lsh)
    p_ps = jax.jit(make_krr_predict(mesh, cfg, f))(xt, lsh, t_ps)
    p_hj = jax.jit(make_krr_predict_hashjoin(
        mesh, cfg, f, payload_dtype=jnp.float32))(xt, lsh, t_hj)
    np.testing.assert_allclose(np.asarray(p_hj), np.asarray(p_ps), atol=1e-5)


@needs_4
def test_hashjoin_step_4shards_matches_psum_in_process():
    """4-way data-sharded hash-join parity, in-process (CI multidevice job):
    real all_to_alls over 4 shards, f32 wire, <= 1e-4 against the psum
    step on the same mesh."""
    from repro.compat import make_mesh
    from repro.core.distributed import make_krr_step, make_krr_step_hashjoin
    x, y, lsh, f = _hj_problem(n=256, table_size=1024)
    mesh = make_mesh((1, 4, 1), ("pod", "data", "model"))
    cfg = _hj_cfg(table_size=1024)
    b_ps, _, _ = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
    b_hj, _, _, _ = jax.jit(make_krr_step_hashjoin(
        mesh, cfg, f, cap_factor=4.0, payload_dtype=jnp.float32))(x, y, lsh)
    err = float(jnp.max(jnp.abs(b_hj - b_ps)))
    assert err <= 1e-4, err


_BLOCKED_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import sample_lsh_params, GammaPDF, get_bucket_fn, featurize
from repro.core.wlsh import build_table_index, table_matvec
from repro.core.krr import cg_solve
from repro.core.distributed import KRRStepConfig, make_krr_step

assert len(jax.devices()) == 2
mesh = make_mesh((1, 2, 1), ("pod", "data", "model"))
n, d, m, B = 256, 4, 4, 1024
key = jax.random.PRNGKey(0)
x = jax.random.uniform(key, (n, d)) * 2.0
y = jax.random.normal(jax.random.PRNGKey(1), (n,))
lsh = sample_lsh_params(jax.random.PRNGKey(2), m, d, GammaPDF(2.0, 1.0))
f = get_bucket_fn("rect")
cfg = KRRStepConfig(m=m, table_size=B, lam=0.5, cg_iters=20,
                    data_axes=("pod", "data"), model_axis="model",
                    backend="pallas")
beta, resnorm, tables = jax.jit(make_krr_step(mesh, cfg, f))(x, y, lsh)
idx = build_table_index(featurize(lsh, f, x), B)
ref = cg_solve(lambda v: table_matvec(idx, v), y, 0.5, tol=0.0, maxiter=20)
err = float(jnp.max(jnp.abs(jax.device_get(beta) - ref.x)))
assert err < 1e-4, f"blocked-split sharded step mismatch {err}"
print("BLOCKED_SPLIT_OK", err)
"""


@pytest.mark.slow
def test_blocked_split_krr_step_two_shards_subprocess():
    """The pallas blocked-split psum path on a real 2-device data mesh
    agrees with the single-device reference solve (subprocess tier, so it
    also runs where the pytest process only sees one device)."""
    proc = subprocess.run(
        [sys.executable, "-c", _BLOCKED_SCRIPT],
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
             "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, cwd=".", timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BLOCKED_SPLIT_OK" in proc.stdout


@pytest.mark.slow
def test_hashjoin_krr_matches_psum_mode():
    """The beyond-paper hash-join table mode solves the same system as the
    paper-faithful psum mode (generous routing capacity => no drops)."""
    proc = subprocess.run(
        [sys.executable, "-c", _HJ_SCRIPT],
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, cwd=".", timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "HASHJOIN_OK" in proc.stdout
