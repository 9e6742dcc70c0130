"""What every cell shares: finding its files by name, the seed's keys, the
device, the window's clock and compile count, and the program's counters.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``).
The mix's ``kind`` names the driver module that runs it,
``chipbench/<kind>.py`` (``fit_loop``, ``open_loop``); per-layer metrics
are readers in ``metrics/<metric>.py``.  Adding a cell, a mix or a metric
adds files and entries and edits none of these.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"


def prepare_process() -> None:
    """Put the program on the path and JAX's persistent compile cache at
    its one fixed place inside the checkout, before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def cache_every_program() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic mix,
    end-to-end metrics and per-layer metrics."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench if bench is not None else load_json(
            ROOT / "BENCHMARK.json")
        byname = {w["name"]: w for w in bench["workloads"]}
        if name not in byname:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(byname)}")
        self.name = name
        self.workload = byname[name]
        conf = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(ROOT / conf["file"])
        self.traffic = load_json(HERE / "traffic" /
                                 f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self):
        return importlib.import_module(f"chipbench.{self.traffic['kind']}")


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seed_key(seed: int, *path: int):
    """A PRNG key for ``seed`` (any whole number below 2**64) and a path
    of stream ids under it."""
    import jax
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFF_FFFF, seed >> 32) + path:
        key = jax.random.fold_in(key, word)
    return key


def seed_rng(seed: int, *path: int):
    """A numpy Generator for ``seed`` and a path of stream ids."""
    import numpy as np
    return np.random.default_rng([seed & 0xFFFF_FFFF, seed >> 32, *path])


class NoChip(RuntimeError):
    pass


def require_chip(chips: int):
    """The devices to measure on; raises ``NoChip`` unless JAX finds a TPU
    with at least ``chips`` devices."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r}); the "
                     f"benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def counters(names) -> dict:
    """Sum over label children of the program's registry counters."""
    from repro import obs
    snap = obs.REGISTRY.snapshot()
    return {name: float(sum(s["value"] for s in
                            snap.get(name, {}).get("series", [])))
            for name in names}


COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                  "/jax/core/compile/backend_compile_duration": "compiles"}


class CompileCount:
    """Traces and backend compiles (loads from the persistent cache
    included) that JAX records while this is open: a window after a full
    warm-up should have none."""

    def __enter__(self):
        import jax.monitoring
        self.counts = dict.fromkeys(COMPILE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **kwargs):
        if event in COMPILE_EVENTS:
            self.counts[COMPILE_EVENTS[event]] += 1

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


def now() -> float:
    return time.perf_counter()


def annotation(name: str):
    """A host span of the benchmark's own on the profiler's timeline."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def no_annotation(name: str):
    return contextlib.nullcontext()


def start_trace(directory: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no Python calls
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()
