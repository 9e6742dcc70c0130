"""Shared benchmark utilities: wall-clock timing + CSV emission, and the
guard of the fake-CPU-mesh sections.

Timing goes through ``repro.obs`` spans so every benchmark sample also lands
in the span buffers and the ``bench_us`` histogram — the benchmarks and the
live /metrics endpoint report from the SAME clock and recording path, and a
profiler trace of a bench run shows each sample as a named annotation.
"""
from __future__ import annotations

from typing import Callable

import jax

from repro import obs


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3,
            stat: str = "median", span: str = "bench.time_fn") -> float:
    """Wall time (seconds) of fn(*args) after warmup (jit-friendly).

    ``stat='median'`` is the honest trajectory statistic; ``stat='min'`` is
    the noise-robust one for regression gating — on shared CPU containers
    the timing distribution is bimodal (noisy-neighbor bursts 2-3x the quiet
    mode), and only the minimum is reproducible run to run.

    Each timed iteration is recorded as an obs span named ``span``
    (block_until_ready INSIDE the span, so the sample covers device work);
    callers can pull the full sample set back via
    ``obs.span_samples_us(span)`` instead of re-timing.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    hist = obs.histogram("bench_us", "benchmark sample wall time",
                         labels=("name",)).labels(span)
    for _ in range(iters):
        with obs.span(span, to_histogram=hist):
            jax.block_until_ready(fn(*args))
    times = [s / 1e6 for s in obs.span_samples_us(span)[-iters:]]
    if stat == "min":
        return min(times)
    if stat == "median":
        return sorted(times)[len(times) // 2]
    raise ValueError(f"unknown stat {stat!r}")


def emit(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def refuse_on_tpu(section: str) -> None:
    """Refuse code that runs fake-CPU meshes in child processes.

    On a TPU host this process already holds the chip, and the children are
    forced onto the CPU: they would run and time the CPU and say nothing
    about the device.  ``chip_smoke.py --chips 4`` runs the sharded paths
    on chips."""
    if jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            f"{section} runs fake-CPU meshes in child processes, which on a "
            f"TPU host would run on the CPU; run it on a CPU host "
            f"(JAX_PLATFORMS=cpu), and `python chip_smoke.py --chips 4` for "
            f"the sharded paths on the chip")
