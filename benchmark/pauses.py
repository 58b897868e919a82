"""Whole-machine pauses beside a training window, and the one rule by which
``train_tok_s_chip`` leaves out the steps they hit.

The machine that holds the chips now and then stops for 1-2 s, every process
at once (PERF.md, PR 29): a window that meets such a pause loses one or two
steps' worth of seconds, 2-7% of its rate, and no program can repair that.
So a WATCHER runs beside every window: a process of its own that imports
neither jax nor ray_tpu (it is started ``python -I -S``: only the standard
library can be imported at all), sleeps ``SLEEP_S`` in a loop and records
every sleep that overshot by more than ``OVERSHOOT_S`` as a pause
``[start, end]``. It must be a process and not a thread: a thread in the
worker or the runner shares a GIL with the code under test and would read
the program's own stalls as pauses, and forgive them.

The stamps are ``time.monotonic()``, which on Linux is ``CLOCK_MONOTONIC``:
one clock for every process of a host (it counts from the kernel's boot, not
from the process's start), so the watcher's stamps and the loop function's
``t_a``..``t_d`` can be compared as they are. That holds on ONE host only:
the worker reports ``clock_id()`` (the kernel's boot id) and pauses from a
watcher with another are not used.

The rule (``judge``): a step of the window, its whole interval ``t_a`` to
``t_d`` (data wait + fenced step + report), is left out of the rate only if
(a) the interval overlaps a recorded pause AND (b) it took longer than the
median of the window's steps by at least ``PAID_SHARE`` of that pause's
length. Left out of the numerator (its tokens) and of the denominator (its
seconds). A slow step with no pause beside it stays in: that is the
program's own stall. A step beside a pause that is no longer than the others
stays in too: the chip finishes the step in flight while the host stands
still, so a step pays a pause's length LESS what was left of its device time
(a 1.5 s stop cost a 817 ms step 0.77-1.35 s; PERF.md, PR 30), and one that
paid nothing lost nothing.
The cap: where the seconds left out pass ``CAP_SHARE`` of the window, or
what is left is under ``CAP_LEFT`` x ``--seconds``, nothing is left out. A
pause never fails a run: every path here ends in a number.

Run as a script this file IS the watcher (``Watcher`` starts it so).
"""

from __future__ import annotations

import json
import os
import signal
import statistics
import subprocess
import sys
import tempfile
import time

SLEEP_S = 0.05      # the watcher's sleep
OVERSHOOT_S = 0.25  # a sleep that took this much longer is a pause
SHORT_S = 0.02      # one that took this much longer is shown, and changes nothing
PAID_SHARE = 0.1    # of a pause's length: a step longer by this much paid for it
CAP_SHARE = 0.15    # of window_s: more seconds than this are never left out
CAP_LEFT = 0.8      # of --seconds: at least this much of a window is counted


def clock_id() -> str:
    """Names the clock ``time.monotonic()`` reads here: the kernel's boot
    id, the same in every process (and container) of one running kernel."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return ""


def watch(out=sys.stdout) -> None:
    """The watcher's loop: a line for its start, one for every pause (and
    for every shorter overshoot, which only the report shows), one for its end. Ends on SIGTERM, or when the process that started it is
    gone (so a killed benchmark leaves no watcher behind)."""
    parent, stop = os.getppid(), []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(True))

    def say(obj: dict) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    say({"started": time.monotonic(), "clock_id": clock_id()})
    while not stop and os.getppid() == parent:
        t0 = time.monotonic()
        time.sleep(SLEEP_S)
        t1 = time.monotonic()
        if t1 - t0 - SLEEP_S > SHORT_S:
            say({"pause" if t1 - t0 - SLEEP_S > OVERSHOOT_S else "short": [t0, t1]})
    say({"stopped": time.monotonic()})


class Watcher:
    """Starts the watcher process; ``stop()`` ends it and returns what it
    saw. Neither raises: with no watcher there are no pauses, and no step
    is left out."""

    def __init__(self):
        self.proc, self.problem = None, None
        try:
            # an unlinked file under TMPDIR, not a pipe: no reader is needed
            # while the window runs, and nothing can fill up
            self.out = tempfile.TemporaryFile("w+")
            self.proc = subprocess.Popen(
                [sys.executable, "-I", "-S", os.path.abspath(__file__)],
                stdin=subprocess.DEVNULL, stdout=self.out, stderr=subprocess.DEVNULL)
        except OSError as e:
            self.problem = f"not started: {e}"

    def stop(self) -> dict:
        """{"started", "stopped", "clock_id", "pauses": [[start, end], ...],
        "short": the same of the overshoots under ``OVERSHOOT_S``} on
        ``time.monotonic()``; ``problem`` where the watcher did not run to
        its end (what it recorded until then still counts)."""
        seen = {"started": None, "stopped": None, "clock_id": None, "pauses": [], "short": []}
        if self.proc is not None:
            try:
                self.proc.terminate()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired) as e:
                self.proc.kill()
                self.proc.wait()
                self.problem = f"did not stop: {e}"
            self.out.seek(0)
            for line in self.out:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue  # a line cut short by a kill
                for kind, into in (("pause", "pauses"), ("short", "short")):
                    if kind in obj:
                        seen[into].append(obj.pop(kind))
                seen.update(obj)
            self.out.close()
            if seen["stopped"] is None and not self.problem:
                self.problem = f"ended early, exit code {self.proc.returncode}"
            self.proc = None
        if self.problem:
            seen["problem"] = self.problem
        return seen


def length_s(pause) -> float:
    """How long the machine stood still: the sleep's overshoot."""
    return pause[1] - pause[0] - SLEEP_S


def judge(starts, lengths, pauses, *, window_s: float, seconds: float) -> dict:
    """The rule and the cap (module docstring). ``starts``: each step's
    ``t_a``; ``lengths``: its ``t_d - t_a`` in seconds; ``pauses``:
    ``[[start, end], ...]`` on the same clock. Returns ``excluded`` (indices
    of the steps left out), ``excluded_s`` (their seconds, 0.0 with none) and
    ``over_cap`` (steps met the rule but were too many to leave out)."""
    hit = []
    if pauses and lengths:
        median = statistics.median(lengths)
        for i, (t_a, took) in enumerate(zip(starts, lengths)):
            beside = [p for p in pauses if p[0] < t_a + took and p[1] > t_a]
            if any(took - median >= PAID_SHARE * length_s(p) for p in beside):
                hit.append(i)
    hit_s = sum((lengths[i] for i in hit), 0.0)
    over = bool(hit) and (hit_s > CAP_SHARE * window_s
                          or window_s - hit_s < CAP_LEFT * seconds)
    if over:
        hit, hit_s = [], 0.0
    return {"excluded": hit, "excluded_s": hit_s, "over_cap": over}


def window_report(m: dict, watched: dict, *, tokens_per_step: int, chips: int,
                  seconds: float) -> dict:
    """What both training runners make of a window. ``m`` is the loop
    function's report (``steps``, ``window_s``, ``t_window_start_mono``,
    ``clock_id``, and per step ``step_t_a``, ``data_wait_ms``, ``step_ms``,
    ``report_ms``), ``watched`` is ``Watcher.stop()``'s. Returns
    ``train_tok_s_chip``, the means of ``data_wait_ms`` and ``report_ms``
    over the same steps, and ``said``: the fields for an earlier line."""
    n, window_s, t_w0 = m["steps"], m["window_s"], m["t_window_start_mono"]
    parts = list(zip(m["data_wait_ms"], m["step_ms"], m["report_ms"]))
    lengths = [sum(p) / 1e3 for p in parts]
    same_clock = bool(watched["clock_id"]) and watched["clock_id"] == m["clock_id"]
    verdict = judge(m["step_t_a"], lengths, watched["pauses"] if same_clock else [],
                    window_s=window_s, seconds=seconds)
    left_out = set(verdict["excluded"])

    def rate(steps: int, over_s: float) -> float:
        return steps * tokens_per_step / over_s / chips

    def shown(pauses) -> list:
        return [[p[0] - t_w0, length_s(p)] for p in pauses]

    said = {
        # each [start - window start, seconds lost]; set-up's start below 0
        "pauses": shown(p for p in watched["pauses"] if p[1] > t_w0),
        "pauses_in_setup": shown(p for p in watched["pauses"] if p[1] <= t_w0),
        "steps_excluded": verdict["excluded"], "excluded_s": verdict["excluded_s"],
        "train_tok_s_chip_all_steps": rate(n, window_s),
        "pauses_over_cap": verdict["over_cap"],
        # overshoots under OVERSHOOT_S inside the window, the ten longest:
        # shown so that a window of many small stalls can be told from a
        # slow program; no step is left out for them
        "short_overshoots": sorted(shown(
            p for p in watched["short"] if p[1] > t_w0 and p[0] < t_w0 + window_s),
            key=lambda p: -p[1])[:10],
        "watcher": {"clock_shared": same_clock, "problem": watched.get("problem"),
                    "watched_s": watched["stopped"] and watched["stopped"] - watched["started"]},
        # where a stall inside the window sits, pause or not: the three
        # slowest steps as [index, step ms, data wait ms, report ms], and
        # the window's time outside the three timers
        "slowest_steps": [[i, parts[i][1], parts[i][0], parts[i][2]]
                          for i in sorted(range(n), key=lambda i: -lengths[i])[:3]],
        "window_s_outside_timers": window_s - sum(lengths),
    }
    counted = [i for i in range(n) if i not in left_out]
    return {"train_tok_s_chip": rate(len(counted), window_s - verdict["excluded_s"]),
            # per-step means over the steps that count: a pause inside one
            # next(batches) is the machine's, not the data path's
            "data_wait_ms": sum(parts[i][0] for i in counted) / len(counted),
            "report_ms": sum(parts[i][2] for i in counted) / len(counted),
            "said": said}


if __name__ == "__main__":
    watch()
