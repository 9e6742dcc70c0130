#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (a ``workloads`` entry of BENCHMARK.json) names its configuration
and traffic mix; their files, and the per-layer metric readers, are found
by name under ``chipbench/``.  One process: set-up (data and model from
the seed, every shape the window uses compiled or loaded from the compile
cache in ``.jax_cache/``), the measured window, the peak memory, then the
check of every answer the window produced against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
also printed as the last lines of standard error.  Without a TPU, or with
fewer chips than the cell needs, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402

WINDOW_SPAN = "chipbench.window"


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, system=None, t_start: float | None = None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    line as a dict.  ``system`` stands in for the program (the control,
    or a test's broken program)."""
    t_start = T_START if t_start is None else t_start
    drv = cell.driver()
    st = drv.setup(cell, seed, seconds, system)
    setup_s = harness.now() - t_start
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_")) \
        if trace else None
    if trace:
        from repro import obs
        obs.set_jax_annotations(True)
        harness.start_trace(trace_dir)
    try:
        with harness.CompileCount() as compiled:
            win = drv.measure(st, seconds, trace)
    finally:
        if trace:
            harness.stop_trace()
    device = harness.device_info(devices)
    win = drv.release(st, win)
    checks = drv.check(st, win)
    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": bool(correct), "attempted": win.attempted,
              "failed": win.failed}
    if trace:
        from chipbench import trace as tr
        red = tr.reduce_dir(str(trace_dir), WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = RunInfo(cell, st, win, red, drv.layer_info(st, win),
                      device["kind"])
        metrics = {}
        for m in cell.per_layer:
            value = harness.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics
        result["breakdown"] = red.breakdown()
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": _number(e2e[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["notes"] = dict(drv.layer_info(st, win),
                           window_compiles=compiled.counts)
    result["checks"] = {k: {"value": _number(v), "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _number(v):
    """A finite number as it is; NaN or infinity (nothing answered, so
    nothing to time or compare) as null, which JSON can carry."""
    return v if math.isfinite(v) else None


class RunInfo:
    """What a per-layer metric reader gets: the cell, its set-up state,
    the window, the trace reduction and the driver's layer counts."""

    def __init__(self, cell, state, window, reduction, info, device_kind):
        self.cell, self.state, self.window = cell, state, window
        self.trace, self.info = reduction, info
        self.device_kind = device_kind


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.prepare_process()
    cell = harness.Cell(args.workload)
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.cache_every_program()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    # the layers' counts and the generator's lateness, before the checks
    print(f"notes {json.dumps(result['notes'])}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    # nothing may print after the result: skip interpreter teardown, whose
    # runtime shutdown can write to standard error
    os._exit(0)


if __name__ == "__main__":
    raise SystemExit(main())
