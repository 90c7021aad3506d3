"""Reduction of a JAX profiler trace to device time, idle share, kernel
time and the breakdown.

The harness wraps its measured window in one host annotation
(:data:`WINDOW`) and notes the host clock (``time.perf_counter``) as it
enters and leaves it, which puts the program's ``obs`` spans, taken on
that clock, onto the profiler's. A trace is first flattened to plain
data (:func:`flatten`): planes of lines of ``[name, start_ns,
duration_ns]`` events, which is also the form of the small recorded
trace the tests check this module on.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: Host annotation that spans the measured window.
WINDOW = "bench.window"
#: The device line whose events are the operations that ran.
OPS_LINE = "XLA Ops"
#: Entries in each list of the breakdown.
TOP = 10

Plane = Dict[str, object]


def flatten(path: str) -> List[Plane]:
    """The ``.xplane.pb`` trace under ``path`` as plain data:
    ``[{"name", "lines": [{"name", "events": [[name, start, dur]]}]}]``,
    keeping the device planes, with each operation named by
    :func:`op_name`, and of the host's events only :data:`WINDOW`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profiler trace under {path}, "
                           f"found {len(files)}")
    planes: List[Plane] = []
    for plane in ProfileData.from_file(files[0]).planes:
        device = is_device(plane.name)
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == WINDOW]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(text: str) -> str:
    """A device event's operation name without its HLO text or its
    numeric suffix: ``"%simjoin_pruned.4 = s32[1] custom-call(...)"``
    becomes ``"simjoin_pruned"``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def is_device(name: str) -> bool:
    """Whether a plane is an accelerator's (``/device:TPU:0`` and
    alike; the host's planes are ``/host:...``)."""
    return name.startswith("/device:") and "CPU" not in name


def window_ns(planes: Sequence[Plane]) -> Tuple[float, float]:
    """``(start, end)`` of the :data:`WINDOW` annotation."""
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def device_ops(planes: Sequence[Plane]) -> Dict[str, List[list]]:
    """Device plane name -> its :data:`OPS_LINE` events."""
    out = {}
    for plane in planes:
        if not is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = line["events"]
    return out


def clipped(events: Sequence[list], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    """Sorted ``(start, end)`` intervals of ``events`` cut to
    ``[lo, hi]``."""
    out = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e))
    out.sort()
    return out


def self_times(events: Sequence[list], lo: float, hi: float
               ) -> List[Tuple[str, float]]:
    """``(name, self time)`` of each event cut to ``[lo, hi]``: its
    length less that of the events nested in it (a loop's body runs
    inside the loop's own event)."""
    cut = sorted((max(s, lo), min(s + d, hi), n) for n, s, d in events
                 if min(s + d, hi) > max(s, lo))
    cut.sort(key=lambda ev: (ev[0], -ev[1]))
    own = [e - s for s, e, _ in cut]
    stack: List[int] = []
    for k, (s, e, _) in enumerate(cut):
        while stack and cut[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(k)
    return [(n, own[k]) for k, (_, _, n) in enumerate(cut)]


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge sorted intervals into disjoint ones."""
    merged: List[List[float]] = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between disjoint busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[str, float, float]],
              points: Sequence[float]) -> List[str]:
    """For each of ``points``, the innermost of the nested ``spans``
    (``(name, start, end)``) open at it, or ``"between queries"``.
    One sweep over both, sorted by time."""
    order = sorted(range(len(points)), key=lambda k: points[k])
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out = ["between queries"] * len(points)
    stack: List[Tuple[str, float, float]] = []
    i = 0
    for k in order:
        t = points[k]
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        if stack:
            out[k] = stack[-1][0]
    return out


def reduce(planes: Sequence[Plane], kernel: str,
           spans: Sequence[Tuple[str, float, float]] = (),
           devices: Optional[int] = None) -> dict:
    """Window length, device busy time averaged over ``devices`` (all
    device planes with operations when ``None``), the
    summed device time of events whose name matches ``kernel`` (a
    regular expression), the device operations that took most self
    time (:func:`self_times`), and
    the device's idle time summed by what the host was doing: each idle
    gap goes to the innermost of the ``spans`` (``(name, start, end)``
    on the profiler's clock) open at its midpoint, and the sums are
    averaged over the devices like the busy time."""
    lo, hi = window_ns(planes)
    ops = device_ops(planes)
    if not ops:
        raise ValueError("no device plane with an 'XLA Ops' line")
    pattern = re.compile(kernel)
    ops = {plane: [[op_name(n), s, d] for n, s, d in events]
           for plane, events in ops.items()}
    busy_ns, kernel_ns = 0.0, 0.0
    by_name: Dict[str, float] = {}
    idle: List[Tuple[float, float]] = []
    for events in ops.values():
        busy = union(clipped(events, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        idle += gaps(busy, lo, hi)
        for name, own in self_times(events, lo, hi):
            by_name[name] = by_name.get(name, 0.0) + own
        for ev in events:
            if pattern.search(ev[0]):
                kernel_ns += sum(e - s for s, e in clipped([ev], lo, hi))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    idle_by: Dict[str, float] = {}
    for (s, e), name in zip(idle, innermost(spans, [(s + e) / 2
                                                    for s, e in idle])):
        idle_by[name] = idle_by.get(name, 0.0) + (e - s)
    idle_top = sorted(idle_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / (devices or len(ops)),
        "devices": len(ops),
        "kernel_s": kernel_ns * 1e-9,
        "device_ops": [[n, ns * 1e-9] for n, ns in top],
        "idle_gaps": [[n, ns * 1e-9 / (devices or len(ops))]
                      for n, ns in idle_top],
    }


def spans_on_profile_clock(spans, enter_s: float, start_ns: float
                           ) -> List[Tuple[str, float, float]]:
    """Closed ``obs`` spans as ``(name, start_ns, end_ns)`` on the
    profiler's clock, given the host clock ``enter_s`` read as the
    :data:`WINDOW` annotation opened at ``start_ns``."""
    return [(s.name, start_ns + (s.start - enter_s) * 1e9,
             start_ns + (s.end - enter_s) * 1e9)
            for s in spans if s.end is not None]
