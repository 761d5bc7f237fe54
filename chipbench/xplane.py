"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
device operation, the device time of the serving plans, and idle gaps
labelled by what the host was doing.

The traced window is the union of the host annotations
``chipbench.window`` that the benchmark opens around the timed part of
each flush it traces.  A device's busy time is the union of the
intervals of its ``XLA Ops`` events within the window;
a plan's time is the union of the ops that start inside a run of an
``XLA Modules`` event named in ``plan_modules``.  Idle gaps are labelled
by the benchmark's own ``chipbench.*`` annotation open at their
midpoint, else ``host.other``.
"""
from __future__ import annotations

import bisect
import collections
import re

WINDOW = "chipbench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, as sorted disjoint ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def intersect(span, windows, starts) -> list:
    """The parts of ``span`` inside sorted disjoint ``windows``, whose
    starts are ``starts``."""
    s, e = span
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    out = []
    while i < len(windows) and windows[i][0] < e:
        lo, hi = windows[i]
        if hi > s:
            out.append((max(s, lo), min(e, hi)))
        i += 1
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The intervals of ``[lo, hi]`` that ``busy`` (merged) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def _base(module: str) -> str:
    """``jit_call(12)`` -> ``jit_call``."""
    return module.split("(")[0]


def reduce(data, plan_modules=("jit_call",), chips: int = 1) -> dict | None:
    """Busy, plan and per-op seconds of the first ``chips`` devices,
    averaged over them, within the benchmark's traced window.  ``data``
    is a ``jax.profiler.ProfileData``.  None when the trace has no
    window or no device."""
    host = [p for p in data.planes if p.name.startswith("/host:")]
    marks = [(e.start_ns, e.end_ns, e.name) for p in host
             for line in p.lines for e in line.events
             if e.name.startswith("chipbench.")]
    windows = merge((s, e) for s, e, n in marks if n == WINDOW)
    window_starts = [s for s, _ in windows]
    devices = sorted((int(m.group(1)), p) for p in data.planes
                     if (m := _DEVICE.match(p.name)))[:chips]
    if not windows or not devices:
        return None
    labels = sorted((s, e, n) for s, e, n in marks if n != WINDOW)
    starts = [s for s, _, _ in labels]
    busy_s = plan_s = 0.0
    per_op: dict = collections.Counter()
    idle: dict = collections.Counter()
    for _, plane in devices:
        modules = sorted((e.start_ns, e.end_ns, _base(e.name))
                         for e in _events(plane, "XLA Modules"))
        module_starts = [s for s, _, _ in modules]
        ops, plan = [], []
        for e in _events(plane, "XLA Ops"):
            span = intersect((e.start_ns, e.end_ns), windows, window_starts)
            if not span:
                continue
            ops.extend(span)
            per_op[e.name] += total(span) * 1e-9
            # the op belongs to the module whose run holds its start
            i = bisect.bisect_right(module_starts, e.start_ns) - 1
            if i >= 0 and modules[i][1] > e.start_ns \
                    and modules[i][2] in plan_modules:
                plan.extend(span)
        busy = merge(ops)
        busy_s += total(busy) * 1e-9
        plan_s += total(merge(plan)) * 1e-9
        idle_spans = [g for lo, hi in windows
                      for g in gaps(clip(busy, lo, hi), lo, hi)]
        for s, e in idle_spans:
            mid = (s + e) / 2
            # the benchmark's annotations do not nest: the last one that
            # opened before mid holds it, or none does
            i = bisect.bisect_right(starts, mid) - 1
            held = i >= 0 and labels[i][1] >= mid
            idle[labels[i][2] if held else "host.other"] += (e - s) * 1e-9
    n = len(devices)
    return {
        "window_s": total(windows) * 1e-9,
        "busy_s": busy_s / n,
        "plan_op_s": plan_s / n,
        "device_ops": [[k, v / n] for k, v in per_op.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in idle.most_common(10)],
    }
