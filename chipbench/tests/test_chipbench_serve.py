"""The serving cells run end to end on the CPU at a tiny size, past the
look for a chip: sound runs are correct, and the control and each fault a
serving cell can have are caught."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, harness
from chipbench.run import run_cell

TINY = dict(n_train=4096, n_test=64, m=4, table_size=1 << 14)
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _cell(name="forest.serve_uniq", backend="reference"):
    cell = harness.Cell(name)
    cell.config.update(TINY, backend=backend)
    cell.traffic.update(rate_per_s=200.0)
    if "pool" in cell.traffic:
        cell.traffic.update(pool=2048, cache_prefill=256)
    return cell


def _run(cell, system=None, seconds=0.5):
    return run_cell(cell, 2**35 + 3, seconds, False, jax.devices(),
                    system=system, t_start=time.perf_counter())


@pytest.mark.parametrize("name,backend", [
    ("forest.serve_uniq", "pallas"), ("forest.serve_zipf", "reference")])
def test_rehearsal(name, backend):
    res = _run(_cell(name, backend))
    assert list(res)[:5] == list(KEYS) and list(res)[-1] == "checks"
    json.dumps(res, allow_nan=False)        # the line strict JSON reads
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 100
    assert set(res["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                   "setup_s"}
    assert res["checks"]["answer_gap"]["value"] <= \
        res["checks"]["answer_gap"]["limit"]
    if name.endswith("zipf"):
        assert res["notes"]["hits"] > res["notes"]["misses"]


def test_the_bf16_control_is_not_correct():
    cell = _cell()
    res = _run(cell, control.control_system(cell, jnp.bfloat16))
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > \
        res["checks"]["answer_gap"]["limit"]


def _state_unchanged(monkeypatch):
    """Each device batch answers with the previous batch's answers."""
    from repro.serve import predictor
    orig = predictor.Predictor._predict_padded
    last = {}

    def padded(self, hosted, x):
        out = orig(self, hosted, x)
        prev = last.get("out")
        last["out"] = out
        if prev is None:
            return out
        return np.resize(prev, out.shape).astype(out.dtype)
    monkeypatch.setattr(predictor.Predictor, "_predict_padded", padded)


def _half_left_out(monkeypatch):
    """The readout averages the first half of the instances only."""
    from repro.core import operator
    orig = operator.WLSHOperator.predict_from_buckets

    def readout(self, index, tables):
        h = index.slot.shape[0] // 2
        half = index._replace(slot=index.slot[:h], sign=index.sign[:h],
                              weight=index.weight[:h], coeff=index.coeff[:h])
        return orig(self, half, tables[:h])
    monkeypatch.setattr(operator.WLSHOperator, "predict_from_buckets",
                        readout)


def _answer_altered(monkeypatch):
    """One answer in each device batch is off by a thousandth of its
    scale."""
    from repro.serve import predictor
    orig = predictor.Predictor._predict_padded

    def padded(self, hosted, x):
        out = np.array(orig(self, hosted, x))
        out[0] += 1e-3 * max(float(np.abs(out).max()), 1e-6)
        return out
    monkeypatch.setattr(predictor.Predictor, "_predict_padded", padded)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_a_broken_server_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = _run(_cell())
    assert res["correct"] is False
