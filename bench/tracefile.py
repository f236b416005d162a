"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

The profiler writes one plane per device (``/device:TPU:<n>``) beside the
host planes.  On a device plane the line ``XLA Ops`` holds one event per
operation that ran, with its start and duration in nanoseconds on the same
clock as the host planes.  The benchmark brackets its window with two host
annotations (``WINDOW_START`` and ``WINDOW_END``), so the window's bounds
and the device's work are read from one clock.

An operation's event name is its HLO instruction (name, shapes, operands).
A loop is an event that spans the events of its body, so only operations
that hold no other are counted.  ``reduce_trace`` returns, for the window:
its length, the busy time of each device (the union of its operation
intervals, clipped to the window), and the device time of every operation
name summed over the devices.
"""
from __future__ import annotations

import glob
import os

WINDOW_START = "bench_window_start"
WINDOW_END = "bench_window_end"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def leaf_ops(events) -> list:
    """(name, start_ns, end_ns) of the operations that hold no other: a
    loop (``while``) is an event that spans the operations of its body,
    which are events of their own, so only the innermost ones count."""
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events), key=lambda e: (e[0], -e[1]))
    out, stack = [], []            # stack: [start, end, name, has_child]
    for a, b, name in evs:
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            if not top[3]:
                out.append((top[2], top[0], top[1]))
        if stack and b <= stack[-1][1]:
            stack[-1][3] = True
        stack.append([a, b, name, False])
    out.extend((t[2], t[0], t[1]) for t in stack if not t[3])
    return out


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, hi = 0.0, None
    for a, b in sorted(intervals):
        if hi is None or a > hi:
            total += b - a
            hi = b
        elif b > hi:
            total += b - hi
            hi = b
    return total


def _window(planes) -> tuple:
    starts, ends = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_START:
                    starts.append(ev.start_ns)
                elif ev.name == WINDOW_END:
                    ends.append(ev.start_ns + ev.duration_ns)
    if not starts or not ends:
        raise ValueError("the trace has no window marks")
    return min(starts), max(ends)


def reduce_trace(path: str) -> dict:
    """{"window_s", "busy_s" (mean over devices), "devices", "ops"
    {name: seconds}, "calls" {name: count}, "t0_ns" (the window's start on
    the trace clock) and
    "gaps" [(start_ns, end_ns)], the idle stretches of the first device}."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    t0, t1 = _window(planes)
    busy, ops, calls, gaps = [], {}, {}, []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ivals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, a, b in leaf_ops(line.events):
                a, b = max(a, t0), min(b, t1)
                if b <= a:
                    continue
                ivals.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
                calls[name] = calls.get(name, 0) + 1
        busy.append(union_length(ivals) / 1e9)
        if len(busy) == 1:
            gaps = _gaps(ivals, t0, t1)
    if not busy:
        raise ValueError("the trace has no TPU device plane")
    return {"window_s": (t1 - t0) / 1e9, "busy_s": sum(busy) / len(busy),
            "devices": len(busy), "ops": ops, "calls": calls, "t0_ns": t0,
            "gaps": gaps}


def _gaps(ivals, t0, t1) -> list:
    """Idle stretches of one device, (start_ns, end_ns)."""
    out, hi = [], t0
    for a, b in sorted(ivals):
        if a > hi:
            out.append((hi, a))
        hi = max(hi, b)
    if t1 > hi:
        out.append((hi, t1))
    return out
