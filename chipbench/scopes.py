"""What the per-layer readers of the program's named parts share: the PCG
loop and the table kernels on the device, and the program's own host
events.

On a TPU trace a device op's name is its HLO instruction
(``%wlsh_fused_matvec.66 = f32[...] custom-call(...)``).  The program gives
every Pallas kernel a stable name (``kernels/binning``); the table kernels'
names are ``TABLE_KERNELS``.  JAX's name stack (the program's ``wlsh.pcg``
and ``wlsh.matvec.*`` scopes) lives in each op's metadata stats, which
``trace.load`` does not read, so the loop is found by structure: the
``while`` ops that enclose a table kernel.  A fit's other ``while`` ops
(the index build's, the LSH sampling's) enclose none.

Ops nest on a device's op line (a ``while`` holds its body's ops), so time
is always the union of intervals, clipped to the window and averaged over
the devices, never a sum of durations.  A program without the names gives
no loop, and the readers then report nothing.
"""
from __future__ import annotations

import bisect

from chipbench import trace as tr

TABLE_KERNELS = ("wlsh_fused_matvec", "wlsh_blocked_scatter",
                 "wlsh_blocked_gather", "wlsh_table_scatter",
                 "wlsh_readout_gather")


def instruction(e: tr.Event) -> str:
    """The op's HLO instruction name: ``while.5`` of ``%while.5 = ...``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def holds(e: tr.Event, pattern: str) -> bool:
    """The match of ``Reduction.op_seconds``: name or scope."""
    return pattern in e.name or pattern in e.scope


def _inside(inner: list[tr.Event], outer: list[tr.Event]) -> list[tr.Event]:
    """The ops of ``inner`` that lie within an op of ``outer`` (which do
    not overlap one another)."""
    spans = sorted((e.start, e.end) for e in outer)
    starts = [a for a, _ in spans]
    out = []
    for e in inner:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= spans[i][1]:
            out.append(e)
    return out


def table_kernels(ops: list[tr.Event]) -> list[tr.Event]:
    return [e for e in ops if instruction(e).startswith(TABLE_KERNELS)]


def loop_ops(ops: list[tr.Event]) -> list[tr.Event]:
    """The PCG loop: the ``while`` ops that enclose a table kernel."""
    kernels = table_kernels(ops)
    return [w for w in ops if instruction(w).split(".")[0] == "while"
            and _inside(kernels, [w])]


def loop_kernels(ops: list[tr.Event]) -> list[tr.Event]:
    """The table kernels that run inside the PCG loop."""
    return _inside(table_kernels(ops), loop_ops(ops))


def union_seconds(red: tr.Reduction, select) -> float:
    """Mean over the devices of the union of the intervals of the ops that
    ``select(device_ops)`` returns, clipped to the window."""
    total = sum(b - a for ops in red.devices
                for a, b in tr.union(select(ops), red.t0, red.t1))
    return total / len(red.devices) * 1e-9


def host_events(red: tr.Reduction, name: str) -> list[tr.Event]:
    """The program's host events named ``name`` that start in the
    window."""
    return [e for e in tr.host_spans(red.host, name)
            if red.t0 <= e.start < red.t1]


def busy_within(red: tr.Reduction, a: int, b: int) -> float:
    """Device busy seconds inside [a, b] (ns), mean over the devices."""
    return sum(min(y, b) - max(x, a) for busy in red.busy
               for x, y in busy if y > a and x < b) / len(red.busy) * 1e-9
