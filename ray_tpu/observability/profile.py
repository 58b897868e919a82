"""One capture, one clock: a profiler capture reduced to what an operator
asks first. Where was the device busy, on what, and what was the program
doing while it sat idle?

The window is the capture's own ``capture_window`` event (``capture``
puts it there); its ``wall_s`` / ``mono_s`` attributes are that instant on
``time.time()`` and ``time.monotonic()``, so wall-clock spans
(``tracing.record_span``, ``state.list_spans``) land on the trace's clock
too. Program spans are the ``tracing.annotate`` events, which carry the
``tracing.MARK`` stat (so a site in a new layer needs no list here);
device ops are named by their HLO text, which starts with the
instruction's name (``%flash_fwd.18 = ...``) and holds its frontend
attributes, among them the ``rt_scope="stack/attn"`` of the
``tracing.device_scope`` that issued it and the ``rt_pass="remat"`` of
``tracing.with_passes``: the step by scope and by pass (forward, a
``jax.checkpoint``'s second run, backward) is read off the event names
alone. A device plane's lines cover the same time and its op line nests,
so busy time is a union and an op's time its self time.
Independent of ``benchmark/``, which keeps its own reducer.
"""

from __future__ import annotations

import glob
import os
import re
import time
from collections import defaultdict

from .tracing import MARK, PASS_ATTR, SCOPE_ATTR

WINDOW = "capture_window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SCOPE = re.compile(SCOPE_ATTR + r'="([^"]*)"')
_PASS = re.compile(PASS_ATTR + r'="([^"]*)"')
_NS = 1e-9


def capture(logdir: str, seconds: float) -> str:
    """Trace this process for ``seconds``; the window is one event that
    carries both host clocks. Returns ``logdir``."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    # Python frames stay off: hooking every thread cost a saturated serving
    # replica 10.9% idle against 6.75% without, over the same 4 s on a v5e
    # host (PERF.md, PR 24), and the program's own spans cover the gaps.
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW, wall_s=time.time(),
                                          mono_s=time.monotonic()):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return logdir


def limit_s(duration: float) -> float:
    """Seconds to allow each step that follows a capture's ``duration``:
    stopping and exporting the trace in the traced process, then reading it
    in a child. On a saturated serving replica (77,000 device events a
    second on a v5e) the first took 15 s per captured second, 120.6 s for
    8 s, and the second 8.5 s (PERF.md, PR 24); this is four times that.
    Every timeout on the way is this one number plus a margin."""
    return 120.0 + 60.0 * duration


def summarize_apart(path: str, timeout: float) -> dict:
    """``summarize(path)`` in a process of its own, pinned to the CPU.
    Reading a capture walks every event in Python, which a process that
    serves or trains must not spend its interpreter on."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.observability.profile", path],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"summarizing {path} failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.rsplit("\n", 2)[-2])


def _union(intervals, lo, hi):
    """Seconds of [lo, hi) the intervals cover, and the gaps they leave."""
    total, gaps, edge = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= edge:
            continue
        if s > edge:
            gaps.append((edge, s))
            edge = s
        total, edge = total + e - edge, e
    if edge < hi:
        gaps.append((edge, hi))
    return total, gaps


def _self_times(events) -> dict:
    """name -> [self seconds, count]; an event inside another is its child."""
    out, stack = defaultdict(lambda: [0.0, 0]), []

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, covered, start = stack.pop()
            out[name][0] += end - start - covered
            out[name][1] += 1

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return out


def _top(table: dict, n: int) -> list:
    return [[k, *v] for k, v in sorted(table.items(), key=lambda kv: -kv[1][0])[:n]]


def _innermost(spans, t):
    best = None
    for name, s, e, _ in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best and best[0]


def summarize(path: str, spans: list | None = None, top: int = 12) -> dict:
    """Reduce the capture at ``path`` (a ``.xplane.pb`` or a directory that
    holds one). ``spans``: wall-clock span dicts, mapped onto the trace's
    clock through the window's ``wall_s``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = max(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    devices, program, frames, window = [], [], [], None
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(_DEVICE.match(plane.name))
        for line in plane.lines:
            if on_device:  # a plane's lines cover the same time: the op line only
                if line.name == "XLA Ops":
                    devices.append((plane.name, [
                        (e.name, e.start_ns * _NS, (e.start_ns + e.duration_ns) * _NS)
                        for e in line.events if e.duration_ns > 0]))
                continue
            for e in line.events:
                ev = (e.name, e.start_ns * _NS, (e.start_ns + e.duration_ns) * _NS)
                if e.name.startswith("$"):  # a Python frame, if the trace has them
                    frames.append((*ev, None))
                    continue
                stats = dict(e.stats)
                if e.name == WINDOW:
                    window = (*ev[1:], stats)
                elif stats.pop(MARK, None) is not None:
                    program.append((*ev, stats))
    if window is None:
        raise ValueError(f"{path} holds no {WINDOW!r} event: not taken by capture()")
    lo, hi, clocks = window
    shift = lo - float(clocks.get("wall_s", 0.0))
    # wall-clock spans join the table; a gap still goes to an annotate span
    # of THIS process (those may come from any worker of the cluster)
    mapped = [(s["name"], s["start"] + shift, s["end"] + shift, s.get("attrs"))
              for s in spans or () if s["end"] + shift > lo and s["start"] + shift < hi]
    out = {"window": {"start_s": lo, "seconds": hi - lo, **clocks},
           "devices": [], "spans": [], "ops": [], "kernels": [], "scopes": {}, "passes": {},
           "idle_by_span": [], "idle_uncovered": [], "gaps_over_1ms": 0}
    totals = defaultdict(lambda: [0.0, 0])
    for name, s, e, _ in program + mapped:
        totals[name][0] += (min(e, hi) - max(s, lo)) * 1e3
        totals[name][1] += 1
    out["spans"] = [{"name": k, "count": n, "total_ms": ms, "mean_ms": ms / n}
                    for k, (ms, n) in sorted(totals.items())]
    gaps = []
    for i, (name, ops) in enumerate(sorted(devices)):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        busy, dev_gaps = _union([(s, e) for _, s, e in ops], lo, hi)
        out["devices"].append({"name": name, "busy_s": busy, "idle_s": hi - lo - busy})
        if i:
            continue  # op tables and gaps: the first device
        gaps = dev_gaps
        by_name, kernels = defaultdict(lambda: [0.0, 0]), defaultdict(lambda: [0.0, 0])
        # the step by scope: every path, and rolled up to a path's first
        # component (``stack``) and to its last (``mla_q``); "" holds no scope
        scopes = {k: defaultdict(lambda: [0.0, 0]) for k in ("path", "first", "last")}
        # the step by pass ("" is outside ``with_passes``: the optimizer, the
        # compiler's own), and each pass by its scopes' first component
        passes = {k: defaultdict(lambda: [0.0, 0]) for k in ("pass", "pass_first")}
        for op, (sec, n) in _self_times(ops).items():
            inst = op.partition(" = ")[0]
            groups = [by_name[re.sub(r"[.\d]+$", "", inst)]]
            if 'custom_call_target="tpu_custom_call"' in op:
                groups.append(kernels[re.sub(r"^%|[.\d]+$", "", inst)])
            scope = _SCOPE.search(op)
            path = scope.group(1) if scope else ""
            first = path.partition("/")[0]
            which = _PASS.search(op)
            which = which.group(1) if which else ""
            groups += [scopes["path"][path], scopes["first"][first],
                       scopes["last"][path.rpartition("/")[2]],
                       passes["pass"][which], passes["pass_first"][which, first]]
            for g in groups:
                g[0] += sec
                g[1] += n
        out.update(ops=_top(by_name, top), kernels=_top(kernels, top))
        # every row, not the top: the rows partition the busy time
        for name, tables in (("scopes", scopes), ("passes", passes)):
            out[name] = {k: [[*(key if isinstance(key, tuple) else (key,)), sec, n,
                              100.0 * sec / busy if busy else 0.0]
                             for key, sec, n in _top(table, len(table))]
                         for k, table in tables.items()}
    by_span, uncovered = defaultdict(float), defaultdict(float)
    gaps.sort(key=lambda g: g[0] - g[1])
    for s, e in gaps[:300]:  # the longest; the rest are microseconds between ops
        owner = _innermost(program, (s + e) / 2)
        if owner is None:
            uncovered[_innermost(frames, (s + e) / 2) or "no host event"] += e - s
        else:
            by_span[owner] += e - s
        out["gaps_over_1ms"] += e - s > 1e-3
    if gaps[300:]:
        uncovered["(shorter gaps, not attributed)"] = sum(e - s for s, e in gaps[300:])
    out["idle_by_span"] = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    out["idle_uncovered"] = sorted(uncovered.items(), key=lambda kv: -kv[1])[:top]
    return out


def render(summary: dict) -> str:
    """The summary as the text ``cli profile --summary`` prints."""
    w = summary["window"]
    lines = [f"window {w['seconds']:.3f} s (wall {w.get('wall_s', 0):.3f})"]
    lines += [f"  {d['name']}: busy {d['busy_s']:.4f} s, idle {d['idle_s']:.4f} s "
              f"({100 * d['idle_s'] / w['seconds']:.2f}%)" for d in summary["devices"]]
    for title, key in (("device ops (self s, n)", "ops"), ("kernels", "kernels")):
        if summary[key]:
            lines += [title] + [f"  {r[0]:<44} {r[1]:.5f} {r[2]}" for r in summary[key]]
    if any(r[0] for r in summary["scopes"].get("path", ())):  # a scope besides ""
        for title, key in (("device time by scope path (self s, n, % of busy)", "path"),
                           ("by a path's first scope", "first"),
                           ("by a path's last scope", "last")):
            lines += [title] + [f"  {r[0] or '(no scope)':<44} {r[1]:.5f} {r[2]:>6} {r[3]:6.2f}"
                                for r in summary["scopes"][key]]
    if any(r[0] for r in summary.get("passes", {}).get("pass", ())):  # a pass besides ""
        lines += ["device time by pass (self s, n, % of busy)"] + [
            f"  {r[0] or '(no pass)':<44} {r[1]:.5f} {r[2]:>6} {r[3]:6.2f}"
            for r in summary["passes"]["pass"]]
        lines += ["by pass and its scopes' first"] + [
            f"  {r[0] or '(no pass)':<8} {r[1] or '(no scope)':<35} {r[2]:.5f} {r[3]:>6} {r[4]:6.2f}"
            for r in summary["passes"]["pass_first"]]
    lines += ["program spans (n, total ms, mean ms)"] + [
        f"  {s['name']:<28} {s['count']:>6} {s['total_ms']:>10.3f} {s['mean_ms']:>9.4f}"
        for s in summary["spans"]]
    lines += [f"idle by innermost program span ({summary['gaps_over_1ms']} gaps over 1 ms)"]
    lines += [f"  {k:<44} {v:.5f}" for k, v in summary["idle_by_span"]]
    lines += ["idle no span covers, by Python frame"]
    lines += [f"  {k:<44} {v:.5f}" for k, v in summary["idle_uncovered"]]
    return "\n".join(lines)


if __name__ == "__main__":  # python -m ray_tpu.observability.profile <path> [--text]
    import json
    import sys

    result = summarize(sys.argv[1])
    print(render(result) if "--text" in sys.argv[2:] else json.dumps(result))
