#!/usr/bin/env python3
"""Knee sweep of a serving cell: the highest rate it sustains.

    python3 chipbench/sweep.py --workload forest.serve_uniq --seed 5 \\
        --seconds 12 --rates 40,60,80,100,120,140

One set-up, then the cell's open loop at each rate in turn, for
``seconds`` each.  A rate is sustained when the answers keep up with the
offer (answered per second of the schedule >= 95% of the rate) and no
backlog grows (the median latency of the last quarter of requests is
within 1.5x that of the first quarter).  Prints one JSON line per rate and
a last line with the knee and four fifths of it, the rate a cell below
the knee runs at.  The sweep is run once, when a cell is defined; the rate
is written into the cell's traffic file as a number.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, traffic  # noqa: E402

KEEP_UP = 0.95
BACKLOG = 1.5


def step(st, drv, rate: float, seed: int, seconds: float) -> dict:
    rows, due, _ = drv.make_traffic(st.cfg, st.traffic, seed, seconds, rate)
    win = drv.measure(st._replace(rows=rows, due=due), seconds, False)
    out = win.outcome
    lat = out.done - out.due
    q = max(1, len(lat) // 4)
    first, last = np.nanmedian(lat[:q]), np.nanmedian(lat[-q:])
    answered = len(out.latencies())
    span = float(np.nanmax(out.done) - out.due[0]) if answered else np.inf
    return {"rate": rate, "requests": len(due), "failed": win.failed,
            "answered_per_s": answered / span,
            "p50_ms": win.e2e["serve_p50_ms"],
            "p95_ms": win.e2e["serve_p95_ms"],
            "first_quarter_median_ms": float(first * 1e3),
            "last_quarter_median_ms": float(last * 1e3),
            "lateness_p95_ms": traffic.percentile(out.lateness() * 1e3, 95),
            "sustained": bool(answered / span >= KEEP_UP * rate
                              and last <= BACKLOG * first
                              and win.failed == 0)}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    harness.prepare_process()
    cell = harness.Cell(args.workload)
    try:
        harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.cache_every_program()
    drv = cell.driver()
    st = drv.setup(cell, args.seed, args.seconds)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        res = step(st, drv, rate, args.seed + 1 + i, args.seconds)
        print(json.dumps(res), flush=True)
        if res["sustained"]:
            knee = rate
    print(json.dumps({"workload": cell.name, "knee_per_s": knee,
                      "four_fifths_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
