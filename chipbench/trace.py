"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
``load`` keeps two event lists from it:

- device ops: the events of each TPU plane's "XLA Ops" line (one per
  operation that ran on the chip), as (name, start_ns, end_ns, scope),
  where ``scope`` holds the op's ``tf_op`` stat (JAX's name stack) and the
  name of the "XLA Modules" event (the jitted program) that encloses it;
- host events: every event of the host plane's threads (TraceAnnotations
  of the benchmark and the program, runtime calls), scope empty.

``Reduction`` answers, for a window [t0, t1] on that clock: how long the
devices were busy (the union of each one's op intervals, clipped to the
window, averaged over the devices), how much op time fell to ops whose name
or scope holds a pattern, which ops took most time, and what the host was
doing in each idle gap of the first device — the innermost host event that
covers the gap's midpoint names it.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import NamedTuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
HOST_PLANE = "/host:CPU"
NO_HOST_EVENT = "(no host event)"


class Event(NamedTuple):
    name: str
    start: int      # ns
    end: int        # ns
    scope: str = ""


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> tuple[dict[str, list[Event]], list[Event]]:
    """({device plane name: op events}, host events) of one xplane file."""
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path))


def _span(ev, scope: str = "") -> Event:
    start = int(ev.start_ns)
    return Event(ev.name, start, start + int(ev.duration_ns), scope)


def _device_ops(plane) -> list[Event] | None:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    modules = sorted(_span(ev) for ev in lines[MODULES_LINE].events) \
        if MODULES_LINE in lines else []
    starts = [m.start for m in modules]
    ops = []
    for ev in lines[OPS_LINE].events:
        op = _span(ev)
        words = [str(v) for k, v in ev.stats if k == SCOPE_STAT]
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i].end:
            words.append(modules[i].name)
        ops.append(op._replace(scope=" ".join(words)))
    return ops


def events_of(data) -> tuple[dict[str, list[Event]], list[Event]]:
    """({device plane name: op events}, host events) of a ``ProfileData``."""
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[
                len(DEVICE_PREFIX):].isdigit():
            ops = _device_ops(plane)
            if ops is not None:
                devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_span(ev) for ev in line.events)
    return devices, host


def union(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Merged intervals covered by ``events``, clipped to [t0, t1]."""
    spans = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                   if e.end > t0 and e.start < t1)
    out: list[list[int]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Reduction:
    """Device busy time, op time by name and idle gaps by host activity
    over the window [t0, t1] (ns), for the ops of one or more devices
    (``devices``: one op list each).  Times are means over the devices."""

    def __init__(self, devices: list[list[Event]], host: list[Event],
                 t0: int, t1: int):
        self.devices = [[e for e in ops if e.end > t0 and e.start < t1]
                        for ops in devices]
        self.host = host
        self.t0, self.t1 = t0, t1
        self.busy = [union(ops, t0, t1) for ops in self.devices]

    def _clipped(self, e: Event) -> int:
        return min(e.end, self.t1) - max(e.start, self.t0)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(b - a for busy in self.busy for a, b in busy) \
            / len(self.busy) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Duration of the ops whose name or scope contains ``pattern``,
        clipped to the window."""
        return sum(self._clipped(e) for ops in self.devices for e in ops
                   if pattern in e.name or pattern in e.scope) \
            / len(self.devices) * 1e-9

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, int] = {}
        for ops in self.devices:
            for e in ops:
                tot[e.name] = tot.get(e.name, 0) + self._clipped(e)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / len(self.devices) * 1e-9] for name, ns in top]

    def gaps(self) -> list[tuple[int, int]]:
        edges = [self.t0] + [x for ab in self.busy[0] for x in ab] + [self.t1]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def idle_by_host(self, k: int = 10) -> list[list]:
        """Idle time of the first device summed by the innermost host event
        covering each gap's midpoint, largest first."""
        host = [e for e in self.host if e.end > self.t0 and e.start < self.t1]
        start = np.array([e.start for e in host], np.int64)
        end = np.array([e.end for e in host], np.int64)
        tot: dict[str, int] = {}
        for a, b in self.gaps():
            mid = (a + b) // 2
            cover = np.flatnonzero((start <= mid) & (mid < end))
            name = (host[cover[np.argmin((end - start)[cover])]].name
                    if len(cover) else NO_HOST_EVENT)
            tot[name] = tot.get(name, 0) + (b - a)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_host()}


def host_spans(host: list[Event], name: str) -> list[Event]:
    return sorted((e for e in host if e.name == name), key=lambda e: e.start)


def reduce_dir(trace_dir: str, window: str) -> Reduction:
    """Load the trace under ``trace_dir`` and reduce every device's ops over
    the host span named ``window`` (the benchmark's own annotation around
    its measured window)."""
    devices, host = load(xplane_file(trace_dir))
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}<n> plane with an "
                         f"'{OPS_LINE}' line in the trace")
    spans = host_spans(host, window)
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    return Reduction([devices[k] for k in sorted(devices)], host,
                     spans[0].start, spans[-1].end)
