"""The benchmark's fixed parts: work counts, the peaks table, the refusal
to measure off the chip, and BENCHMARK.json against the contract it is
read under."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness, roofline

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


def test_matvec_work_counts_the_algorithm():
    flops, nbytes = roofline.matvec_work(35000, 64, 1)
    assert flops == 4 * 64 * 35000
    assert nbytes == 8 * 64 * 35000 + 2 * 4 * 35000
    f4, b4 = roofline.matvec_work(35000, 64, 4)
    assert f4 == 4 * flops and b4 == 8 * 64 * 35000 + 8 * 35000 * 4


def test_peaks_are_keyed_by_device_kind():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError, match="TPU v4"):
        roofline.peaks("TPU v4")
    # the matvec is bound by bandwidth: 8.3e-5 s at the forest_131k fit shape
    least = roofline.least_seconds(*roofline.matvec_work(131072, 64, 1), pk)
    assert least == pytest.approx((8 * 64 * 131072 + 8 * 131072) / 819e9)


def test_command_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "forest_131k.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "platform 'cpu'" in proc.stderr


def test_seed_keys_take_large_seeds():
    a = harness.seed_key(2**40 + 5)
    b = harness.seed_key(5)
    assert a.shape == b.shape and not (a == b).all()
    with pytest.raises(ValueError):
        harness.seed_key(-1)


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_to_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert b["paths"] == ["chipbench"]
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells fits the check's time
    assert 24 * 180 + (2 + 14 * 24) * (b["run_seconds"] + 60) + 1200 \
        <= 43200
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert (ROOT / c["file"]).is_file() and len(c["why"]) <= 200
        assert isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
        names.add(c["name"])
    cells = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (ROOT / "chipbench" / "traffic" /
                f"{w['traffic']}.json").is_file()
        cells[w["name"]] = w
    assert {w["config"] for w in b["workloads"]} == names
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES_E2E and m["better"] in ("lower",
                                                              "higher")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert b["end_to_end"][-1]["name"] == "setup_s"
    layers = {}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= e2e[m["moves"]]
        layers.setdefault(m["layer"], set())
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for name in cells:
        reported = {k for k, ws in e2e.items() if name in ws}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(name in m["workloads"] for m in b["per_layer"])
    all_names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in b[k]]
    assert len(all_names) == len(set(all_names))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_layer_is_named_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for m in _bench()["per_layer"]:
        assert f"`{m['layer']}`" in perf, m["layer"]
