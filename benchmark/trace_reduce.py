"""From a profiler trace (``.xplane.pb``) to numbers.

A device plane carries several lines that cover the same time (steps,
modules, ops), and the op line nests (a ``while`` spans its body). So
busy time is the UNION of the intervals on the op line of one device
plane, never a sum, and an op's time is its self time. The traced window
is the capture's own: ``runners.capture_trace`` wraps the traced work in
a ``TraceAnnotation``, and that host event's start and end, on the
trace's own clock, are the window. Device work is clipped to it, and the
host's time before the first and after the last device operation counts
as idle; the profiler's own start and stop lie outside it. Which planes
and lines are which is data (``trace_profiles/<platform>.json``), read
off a real trace.
"""

from __future__ import annotations

import json
import os
import re
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


def load_profile(platform: str) -> dict:
    with open(os.path.join(HERE, "trace_profiles", platform + ".json")) as f:
        return json.load(f)


def union_seconds(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Length of the union of [start, end) intervals clipped to [lo, hi),
    and the gaps of [lo, hi) it leaves, as (start, end) pairs."""
    total, gaps, edge = 0.0, [], lo
    for start, end in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= edge:
            continue
        if start > edge:
            gaps.append((edge, start))
            edge = start
        total += end - edge
        edge = end
    if edge < hi:
        gaps.append((edge, hi))
    return total, gaps


def self_times(events) -> dict:
    """name -> [self seconds, count] for (name, start, end) events of one
    line, where an event that lies inside another is its child."""
    out = defaultdict(lambda: [0.0, 0])
    stack: list[list] = []  # [name, end, time covered by children, start]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, covered, start = stack.pop()
            out[name][0] += (end - start) - covered
            out[name][1] += 1

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] += end - start
        stack.append([name, end, 0.0, start])
    close(float("inf"))
    return dict(out)


def _events(line):
    for e in line.events:
        if e.duration_ns > 0:
            yield e.name, e.start_ns * NS, (e.start_ns + e.duration_ns) * NS


def _clip(events, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def inflight(events, spec: dict) -> list:
    """The intervals in which an operation ``spec`` describes was running
    or in flight, from (name, start, end) events of one op line: an op
    whose name matches ``ops`` counts for its own duration, and an
    asynchronous one from the start of the event matching ``start`` to
    the end of the next event matching ``done`` with the same first group
    (its number), however much computation ran in between."""
    ops = re.compile(spec.get("ops") or "$^")
    start, done = (re.compile(spec.get(k) or "$^") for k in ("start", "done"))
    out, open_ = [], {}
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        if ops.search(name):
            out.append((s, e))
        m = start.search(name)
        if m:
            open_[m.group(1)] = s
        m = done.search(name)
        if m and m.group(1) in open_:
            out.append((open_.pop(m.group(1)), e))
    return out


def reduce_trace(path: str, profile: dict, unions: dict | None = None) -> dict:
    """The summary every trace-reading metric works from. ``unions``:
    name -> an ``inflight`` spec, each reduced to seconds on device 0."""
    from jax.profiler import ProfileData

    device_re = re.compile(profile["device_plane"])
    op_re = re.compile(profile["op_line"])
    module_re = re.compile(profile["module_line"])
    host_re = re.compile(profile["host_plane"])
    window_re = re.compile(profile["window_event"])
    devices, host_events, windows = [], [], []
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(device_re.search(plane.name))
        ops, modules = [], []
        for line in plane.lines:
            evs = list(_events(line))
            windows.extend(e for e in evs if window_re.search(e[0]))
            evs = [e for e in evs if not window_re.search(e[0])]
            if is_device and op_re.search(line.name):
                ops.extend(evs)
            if is_device and module_re.search(line.name):
                modules.extend(evs)
            if host_re.search(plane.name):
                host_events.extend(evs)
        if is_device and ops:
            devices.append((plane.name, ops, modules))
    if len(windows) != 1:
        raise ValueError(
            f"{len(windows)} events match {profile['window_event']!r} in {path}: "
            f"the capture marks its window with exactly one")
    _, lo, hi = windows[0]
    devices = sorted((name, _clip(ops, lo, hi), _clip(modules, lo, hi))
                     for name, ops, modules in devices)
    if not devices or not devices[0][1]:
        raise ValueError(
            f"no plane matching {profile['device_plane']!r} with a line matching "
            f"{profile['op_line']!r} holds an event inside the window in {path}")
    busy = [union_seconds([(s, e) for _, s, e in ops], lo, hi)[0]
            for _, ops, _ in devices]
    _, ops0, modules0 = devices[0]
    _, gaps = union_seconds([(s, e) for _, s, e in ops0], lo, hi)
    op_self = self_times(ops0)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "devices": len(devices),
        "ops": op_self,                   # name -> [self seconds, count], device 0
        "modules": self_times(modules0),  # the same for programs, device 0
        "unions": {name: union_seconds(inflight(ops0, spec), lo, hi)[0]
                   for name, spec in (unions or {}).items()},
        "device_ops": _top({short_name(k): v[0] for k, v in op_self.items()}),
        "idle_gaps": _top(_attribute(gaps, host_events)),
    }


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(op: str) -> str:
    """A TPU op line names an op by its whole HLO text; for a breakdown
    keep the name, the opcode and a custom call's target."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op
    opcode, target = _OPCODE.search(" " + rest), _TARGET.search(rest)
    return " ".join(x for x in (head, opcode and opcode.group(1),
                                target and target.group(1)) if x)


def _top(seconds_by_name: dict, n: int = 10) -> list:
    rows = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:120], seconds] for name, seconds in rows]


def _attribute(gaps, host_events) -> dict:
    """Idle seconds by what the host was doing: each of the longest gaps
    goes to the shortest host event that covers its middle."""
    out = defaultdict(float)
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        mid, best = (start + end) / 2, None
        for name, s, e in host_events:
            if s <= mid < e and (best is None or e - s < best[1]):
                best = (name, e - s)
        out[best[0] if best else "no host event"] += end - start
    return out


def matching(summary_part: dict, pattern: str) -> tuple[float, int]:
    """Seconds and count of the ops or modules whose name matches."""
    rx = re.compile(pattern)
    rows = [v for k, v in summary_part.items() if rx.search(k)]
    return sum(r[0] for r in rows), sum(r[1] for r in rows)
