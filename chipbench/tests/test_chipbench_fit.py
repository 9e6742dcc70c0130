"""The fit cell run end to end on the CPU at a tiny size, past the look
for a chip: a sound run is correct, and the control and each fault the
cell can have are caught."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import control, harness
from chipbench.run import run_cell

TINY = dict(n_train=512, n_test=64, m=4, table_size=2048)
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _cell(backend="reference"):
    cell = harness.Cell("forest_131k.fit")
    cell.config.update(TINY, backend=backend)
    return cell


def _run(cell, system=None, seconds=0.3):
    return run_cell(cell, 2**33 + 17, seconds, False, jax.devices(),
                    system=system, t_start=time.perf_counter())


def test_rehearsal_with_the_kernels_interpreted():
    cell = _cell("pallas")              # interpret mode off the TPU
    res = _run(cell, seconds=0.5)
    assert list(res)[:5] == list(KEYS) and list(res)[-1] == "checks"
    json.dumps(res, allow_nan=False)        # the line strict JSON reads
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"fit_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert set(res["checks"]) == {"fit_residual", "fit_tables_gap",
                                  "fits_failed"}
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_the_bf16_control_is_not_correct():
    cell = _cell()
    res = _run(cell, control.control_system(cell, jnp.bfloat16))
    assert res["correct"] is False
    assert res["checks"]["fit_residual"]["value"] > \
        res["checks"]["fit_residual"]["limit"]


def _state_unchanged(monkeypatch):
    """PCG returns its initial state: beta = 0."""
    from repro.core import krr
    orig = krr.pcg_solve

    def solve(*a, **kw):
        res = orig(*a, **kw)
        return res._replace(x=jnp.zeros_like(res.x))
    monkeypatch.setattr(krr, "pcg_solve", solve)


def _half_left_out(monkeypatch):
    """The fit sees the first half of the points; the rest get beta 0."""
    from repro.core import krr
    orig = krr.wlsh_krr_fit

    def fit(key, x, y, *a, **kw):
        h = x.shape[0] // 2
        model = orig(key, x[:h], y[:h], *a, **kw)
        return model._replace(beta=jnp.concatenate(
            [model.beta, jnp.zeros((x.shape[0] - h,), model.beta.dtype)]))
    monkeypatch.setattr(krr, "wlsh_krr_fit", fit)


def _answer_altered(monkeypatch):
    """The tables come out of the final loads 1% off in one instance."""
    from repro.core import operator
    orig = operator.WLSHOperator.loads

    def loads(self, index, beta):
        t = orig(self, index, beta)
        return t.at[0].multiply(1.01)
    monkeypatch.setattr(operator.WLSHOperator, "loads", loads)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_a_broken_fit_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = _run(_cell())
    assert res["correct"] is False
