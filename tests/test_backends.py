"""Backend parity: the 'reference' (jnp) and 'pallas' (fused kernel)
implementations of the WLSH operator must agree bit-for-bit on hashes/signs
and to float tolerance on weights/tables/matvecs — including the internal
padding paths (n not a multiple of the point block, table_size not a
multiple of the table tile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import platform_of, resolve_backend, resolve_interpret
from repro.core import (GammaPDF, WLSHKernelSpec, get_bucket_fn, make_operator,
                        sample_lsh_params, wlsh_krr_fit, wlsh_krr_predict)
from repro.core.operator import default_table_size


def _ops(key, n, d, m, table_size, bucket="rect"):
    x = jax.random.uniform(key, (n, d)) * 2.0
    lsh = sample_lsh_params(jax.random.fold_in(key, 1), m, d,
                            GammaPDF(2.0, 1.0))
    f = get_bucket_fn(bucket)
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    ref = make_operator(lsh, f, table_size, backend="reference")
    pal = make_operator(lsh, f, table_size, backend="pallas")
    return x, beta, ref, pal


# n=300 exercises point padding (300 -> 384); n=128 is block-aligned
@pytest.mark.parametrize("n,d,m,table_size", [(128, 2, 3, 256),
                                              (300, 5, 4, 512),
                                              (97, 3, 2, 1024)])
def test_featurize_parity(n, d, m, table_size):
    x, _, ref, pal = _ops(jax.random.PRNGKey(n + d), n, d, m, table_size)
    fr, fp = ref.featurize(x), pal.featurize(x)
    assert fr.key1.shape == fp.key1.shape == (m, n)
    assert bool(jnp.all(fr.key1 == fp.key1))
    assert bool(jnp.all(fr.key2 == fp.key2))
    assert bool(jnp.all(fr.sign == fp.sign))
    np.testing.assert_allclose(fr.weight, fp.weight, atol=2e-6)


@pytest.mark.parametrize("n,table_size", [(300, 512), (128, 256)])
def test_tables_and_matvec_parity(n, table_size):
    x, beta, ref, pal = _ops(jax.random.PRNGKey(7 * n), n, 3, 4, table_size)
    fr = ref.featurize(x)
    # each backend's own layout group: the pallas index drives the kernels
    idx, pidx = ref.build_index(fr), pal.build_index(fr)
    tr, tp = ref.loads(idx, beta), pal.loads(pidx, beta)
    assert tr.shape == tp.shape == (4, table_size)
    np.testing.assert_allclose(tr, tp, atol=1e-4)
    np.testing.assert_allclose(ref.matvec(idx, beta), pal.matvec(pidx, beta),
                               atol=1e-4)
    # sum-mode readout (the distributed path) must agree too
    np.testing.assert_allclose(ref.readout(idx, tr, average=False),
                               pal.readout(pidx, tp, average=False),
                               atol=1e-4)


def test_table_tile_padding_path():
    """table_size not a multiple of the table tile: the layout's tile grid
    covers the padded table and the kernels trim — results must match the
    reference exactly."""
    from repro.core.wlsh import build_blocked_layout, table_loads, \
        table_readout
    from repro.kernels.binning.ops import bin_loads_op, bin_readout_op
    key = jax.random.PRNGKey(11)
    x, beta, ref, _ = _ops(key, 200, 3, 3, 1024)
    idx = ref.build_index(ref.featurize(x), blocked=False)
    # block_t=384 does not divide 1024 -> 3 tiles cover 1152, trim to 1024
    bidx = idx._replace(blocked=build_blocked_layout(
        idx.slot, idx.coeff, 1024, block_t=384, parts="pallas"))
    assert bidx.blocked.num_tiles * 384 == 1152
    tk = bin_loads_op(bidx, beta, interpret=True)
    tr = table_loads(idx, beta)
    assert tk.shape == tr.shape
    np.testing.assert_allclose(tk, tr, atol=1e-4)
    np.testing.assert_allclose(
        bin_readout_op(bidx, tr, interpret=True),
        table_readout(idx, tr), atol=1e-5)


@pytest.mark.parametrize("k", [None, 3])
def test_layoutless_loads_is_table_loads(k):
    """An index without the slot-blocked layout scatters its loads by
    ``table_loads``, bitwise, into (m, B) and (m, B, k) tables, at an n off
    the 128-point block and a table_size off the 512-slot tile; no Pallas
    kernel is traced."""
    from repro.core.wlsh import table_loads
    from repro.kernels.binning.ops import bin_loads_op
    n, table_size = 200, 256
    x, beta, _, pal = _ops(jax.random.PRNGKey(16), n, 3, 4, table_size)
    if k is not None:
        beta = jax.random.normal(jax.random.PRNGKey(17), (n, k))
    idx = pal.featurize_buckets(x)
    assert idx.blocked is None

    def loads(b):
        return bin_loads_op(idx, b, interpret=True)

    got, want = loads(beta), table_loads(idx, beta)
    assert got.shape == want.shape == (4, table_size) + beta.shape[1:]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert "pallas_call" not in str(jax.make_jaxpr(loads)(beta))


@pytest.mark.parametrize("k", [None, 3])
@pytest.mark.parametrize("average", [True, False])
def test_layoutless_readout_is_the_row_gather(k, average):
    """An index without the slot-blocked layout (the serving path's
    ``featurize_buckets``) reads its loads by ``table_readout``'s row gather,
    bitwise, on (m, B) and (m, B, k) tables, at an n off the 128-point block
    and a table_size off the 512-slot tile; no Pallas kernel is traced."""
    from repro.core.wlsh import table_loads, table_readout
    from repro.kernels.binning.ops import bin_readout_op
    n, table_size = 200, 256
    x, beta, _, pal = _ops(jax.random.PRNGKey(14), n, 3, 4, table_size)
    if k is not None:
        beta = jax.random.normal(jax.random.PRNGKey(15), (n, k))
    idx = pal.featurize_buckets(x)
    assert idx.blocked is None
    tables = table_loads(idx, beta)

    def readout(t):
        return bin_readout_op(idx, t, interpret=True, average=average)

    got = readout(tables)
    want = table_readout(idx, tables, average=average)
    assert got.shape == want.shape == beta.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert "pallas_call" not in str(jax.make_jaxpr(readout)(tables))


def test_predict_batched_streams_fixed_blocks():
    """Blocked prediction == whole-set prediction, both backends, including a
    final partial block (n_test % batch_size != 0)."""
    key = jax.random.PRNGKey(3)
    x, beta, ref, pal = _ops(key, 260, 4, 5, 512)
    idx = ref.build_index(ref.featurize(x))
    tables = ref.loads(idx, beta)
    whole = ref.predict_batched(tables, x)
    for op in (ref, pal):
        blocked = op.predict_batched(tables, x, batch_size=64)
        np.testing.assert_allclose(blocked, whole, atol=1e-5)


def test_krr_fit_backend_parity():
    """Acceptance criterion: wlsh_krr_fit(..., backend='pallas') and
    backend='reference' agree to <= 1e-5 on predictions."""
    key = jax.random.PRNGKey(0)
    n, d = 300, 3
    x = jax.random.uniform(key, (n, d)) * 2.0
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    spec = WLSHKernelSpec(bucket=get_bucket_fn("rect"))
    # tight CG tol: compare converged solutions, not mid-trajectory iterates —
    # the fused kernels' accumulation grouping differs by ~1e-7 per matvec,
    # which a loose solve amplifies past the 1e-5 acceptance bar
    fit = lambda backend: wlsh_krr_fit(jax.random.fold_in(key, 2), x, y, spec,
                                       m=24, lam=0.5, maxiter=200, tol=1e-7,
                                       backend=backend)
    m_ref, m_pal = fit("reference"), fit("pallas")
    assert m_ref.backend == "reference" and m_pal.backend == "pallas"
    xq = jax.random.uniform(jax.random.fold_in(key, 3), (77, d)) * 2.0
    p_ref = wlsh_krr_predict(m_ref, xq)
    p_pal = wlsh_krr_predict(m_pal, xq)
    np.testing.assert_allclose(p_ref, p_pal, atol=1e-5)
    # cross-backend serving: pallas-fit model served by the reference backend
    np.testing.assert_allclose(wlsh_krr_predict(m_pal, xq, backend="reference"),
                               p_ref, atol=1e-5)


def test_auto_backend_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_WLSH_BACKEND", raising=False)
    assert resolve_backend("reference") == "reference"
    assert resolve_backend("pallas") == "pallas"
    expected = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert resolve_backend("auto") == expected
    assert resolve_backend(None) == expected
    monkeypatch.setenv("REPRO_WLSH_BACKEND", "pallas")
    assert resolve_backend("auto") == "pallas"      # env overrides auto...
    assert resolve_backend("reference") == "reference"  # ...but not explicit
    with pytest.raises(ValueError):
        resolve_backend("mps")


def test_interpret_follows_placement():
    """The Pallas interpreter is the CPU path only: chosen by default off
    the TPU, never on it, and refused there when asked for."""
    assert resolve_interpret(None, "cpu") is True
    assert resolve_interpret(False, "cpu") is False
    assert resolve_interpret(None, "tpu") is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True, "tpu")
    cpu = jax.devices("cpu")[0]
    mesh = jax.sharding.Mesh(np.array([cpu]), ("data",))
    assert platform_of(mesh) == "cpu"
    assert platform_of(jax.device_put(jnp.zeros(3), cpu)) == "cpu"
    assert platform_of(np.zeros(3)) == jax.devices()[0].platform


def test_compile_cache_directory(monkeypatch):
    """Entry points keep the persistent cache where JAX_COMPILATION_CACHE_DIR
    says and set nothing else; unset, in the repo's git-ignored .jax_cache."""
    import pathlib
    from repro.compile_cache import REPO_CACHE, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert REPO_CACHE == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_default_table_size_heuristic():
    assert default_table_size(1000) == 4096
    assert default_table_size(1024) == 4096
    assert default_table_size(1025) == 8192
    assert default_table_size(1) == 256   # floor at 2^8
