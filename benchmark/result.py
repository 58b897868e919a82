"""The last line of a run: built, checked against the contract, printed.

``emit`` is the only writer of that line. Every way out of a run that has
a result goes through it; a result that fails ``check`` is never printed
and the process ends non-zero instead, as the contract says a failed run
ends.
"""

from __future__ import annotations

import json
import math
import os
import sys

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
EXIT_NO_RESULT = 3


def _finite_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def check(obj, declared: dict[str, str], *, trace: bool,
          chips: int | None = None, platform: str | None = "tpu") -> list[str]:
    """Every way ``obj`` falls short of the contract's last line.

    declared: metric name -> unit, the cell's metrics for this trace mode
    (its ``end_to_end`` ones untraced, its ``per_layer`` ones traced).
    ``chips``/``platform``: what the cell asks for (None: not held to it,
    as in a CPU rehearsal).
    """
    if not isinstance(obj, dict):
        return ["the result is not a JSON object"]
    bad = [f"key {k!r} is missing" for k in REQUIRED if k not in obj]
    if bad:
        return bad
    if not isinstance(obj["correct"], bool):
        bad.append("correct is not true or false")
    for k in ("attempted", "failed"):
        v = obj[k]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{k} is not a count: {v!r}")
    if not bad and obj["failed"] > obj["attempted"]:
        bad.append("more failed than attempted")
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name, unit in declared.items():
        m = metrics.get(name)
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            bad.append(f"metric {name} is missing or has no value and unit")
        elif not _finite_number(m["value"]):
            bad.append(f"metric {name} is not a finite number: {m['value']!r}")
        elif m["unit"] != unit:
            bad.append(f"metric {name} has unit {m['unit']!r}, declared {unit!r}")
    for name in metrics:
        if name not in declared:
            bad.append(f"metric {name} is not declared for this cell and trace mode")
    device = obj["device"]
    if not isinstance(device, dict):
        return bad + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in device:
            bad.append(f"device.{k} is missing")
    if bad:
        return bad
    if platform is not None and device["platform"] != platform:
        bad.append(f"device.platform is {device['platform']!r}, not {platform!r}")
    if not isinstance(device["kind"], str) or not device["kind"]:
        bad.append("device.kind is empty")
    if not isinstance(device["count"], int) or device["count"] < 1:
        bad.append(f"device.count is {device['count']!r}")
    elif chips is not None and device["count"] != chips:
        bad.append(f"device.count is {device['count']}, the cell asks for {chips}")
    if not _finite_number(device["memory_peak_bytes"]) or device["memory_peak_bytes"] < 0:
        bad.append(f"device.memory_peak_bytes is {device['memory_peak_bytes']!r}")
    if trace:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not _finite_number(busy) or not _finite_number(window):
            bad.append(f"a traced run needs device.busy_s and device.window_s: "
                       f"{busy!r}, {window!r}")
        elif not 0 < busy <= window:
            bad.append(f"device.busy_s {busy} is not above 0 and at most "
                       f"window_s {window}")
    breakdown = obj.get("breakdown")
    if breakdown is not None:
        if not trace:
            bad.append("breakdown belongs to a traced run only")
        elif not isinstance(breakdown, dict):
            bad.append("breakdown is not an object")
        else:
            for k in BREAKDOWN_KEYS:
                rows = breakdown.get(k)
                if (not isinstance(rows, list) or len(rows) > 10 or not all(
                        isinstance(r, (list, tuple)) and len(r) == 2
                        and isinstance(r[0], str) and _finite_number(r[1])
                        for r in rows)):
                    bad.append(f"breakdown.{k} is not at most 10 [name, seconds] pairs")
    return bad


def emit(obj, declared: dict[str, str], *, trace: bool, chips: int | None,
         platform: str | None, out_fd: int) -> int:
    """Check ``obj`` and write it as one line to ``out_fd``; the exit code.

    Never raises: a result that cannot be checked or serialised is a
    failed run (a note on stderr, no line on ``out_fd``). The caller has
    already stopped the cluster, so nothing can write after this line,
    and leaves through ``os._exit`` with the code returned.
    """
    try:
        problems = check(obj, declared, trace=trace, chips=chips,
                         platform=platform)
        line = json.dumps(obj, allow_nan=False) if not problems else ""
    except (TypeError, ValueError) as e:
        problems, line = [f"the result cannot be written as JSON: {e}"], ""
    if problems:
        sys.stderr.write("benchmark: no result printed, the last line would "
                         "break the contract:\n  " + "\n  ".join(problems) + "\n")
        sys.stderr.flush()
        return EXIT_NO_RESULT
    data = (line + "\n").encode()
    while data:
        data = data[os.write(out_fd, data):]
    return 0
