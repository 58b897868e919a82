"""``benchmark/pauses.py``: the rule that leaves a step out of
``train_tok_s_chip`` on recorded windows (PR 29's among them), the watcher
process itself, and one rehearsal whose processes are all stopped inside its
window. Every test has a time limit of its own (a subprocess timeout or a
deadline); none is slow."""

import json
import os
import signal
import statistics
import subprocess
import sys
import time

import pytest

from benchmark import pauses, result
from benchmark.manifest import ROOT, Manifest

TOKENS, CHIPS, SECONDS = 16384, 1, 50.0
QUIET_MS = 459.2  # train-4k-moe's median step (PERF.md, PR 29)
CLOCK = "one-host"


def window(step_ms, *, gaps=None, t_w0=5000.0, wait_ms=0.45, report_ms=0.07):
    """The loop function's report for steps of ``step_ms`` run back to back
    from ``t_w0``; ``gaps[i]`` seconds pass outside the timers before step i
    (a traced run's capture start and stop)."""
    t, starts = t_w0, []
    for i, ms in enumerate(step_ms):
        t += (gaps or {}).get(i, 0.0)
        starts.append(t)
        t += (wait_ms + ms + report_ms) / 1e3
    n = len(step_ms)
    return {"steps": n, "window_s": t - t_w0, "t_window_start_mono": t_w0,
            "clock_id": CLOCK, "step_t_a": starts, "step_ms": list(step_ms),
            "data_wait_ms": [wait_ms] * n, "report_ms": [report_ms] * n}


def pause_in(m, step, length_s, offset_s=0.1):
    """A pause of ``length_s`` as the watcher records it: the sleep that
    overshot began ``offset_s`` into ``step``."""
    start = m["step_t_a"][step] + offset_s
    return [start, start + pauses.SLEEP_S + length_s]


def report(m, recorded, seconds=SECONDS, clock=CLOCK, short=None):
    watched = {"started": 0.0, "stopped": 1e6, "clock_id": clock, "pauses": recorded,
               "short": short or []}
    return pauses.window_report(m, watched, tokens_per_step=TOKENS, chips=CHIPS,
                                seconds=seconds)


def old_formula(m):
    return m["steps"] * TOKENS / m["window_s"] / CHIPS


def quiet(n=109, ms=QUIET_MS):
    return [ms + 0.05 * ((7 * i) % 5 - 2) for i in range(n)]


def pr29_step_56():
    steps = quiet(107)
    steps[56] = 1643.43
    m = window(steps)
    return m, [pause_in(m, 56, 1.2)], [56]


def pr29_steps_75_and_79():
    steps = quiet(103)
    steps[75], steps[79] = 1914.96, 1749.64
    m = window(steps)
    return m, [pause_in(m, 75, 1.564), pause_in(m, 79, 1.599)], [75, 79]


def slow_step_no_pause():
    steps = quiet(107)
    steps[40] = 1643.43
    return window(steps), [], []


def slow_step_pause_elsewhere():
    """The program's own stall stays in though the machine paused in the
    same window: the pause lies beside another, normal step."""
    steps = quiet(107)
    steps[40] = 1643.43
    m = window(steps)
    return m, [pause_in(m, 90, 0.3, offset_s=0.01)], []


def pause_beside_a_normal_step():
    """A 1,905 ms step of four chips rode out the pause on the device: the
    interval overlaps it, the step is no longer than the others."""
    m = window(quiet(27, ms=1905.0))
    return m, [pause_in(m, 11, 1.5)], []


def step_longer_by_under_a_tenth_of_the_pause():
    """The device rode out all but 0.1 s of a 1.5 s pause."""
    steps = quiet(109)
    steps[30] += 100.0
    m = window(steps)
    return m, [pause_in(m, 30, 1.5)], []


def step_longer_by_half_the_pause():
    """As the chip showed it (PR 30): a 1.5 s stop cost the 817 ms step in
    flight 0.77 s, the device finished the rest meanwhile."""
    steps = quiet(62, ms=816.7)
    steps[29] = 1588.44
    m = window(steps, wait_ms=0.22, report_ms=0.06)
    return m, [pause_in(m, 29, 1.455)], [29]


def pause_between_two_steps():
    """The capture's start lies between steps 1 and 2 of a traced run, and
    the machine paused there: no step's interval overlaps the pause."""
    m = window(quiet(105), gaps={2: 2.0})
    start = m["step_t_a"][2] - 1.9
    return m, [[start, start + pauses.SLEEP_S + 1.5]], []


RULE_CASES = [pr29_step_56, pr29_steps_75_and_79, slow_step_no_pause,
              slow_step_pause_elsewhere, pause_beside_a_normal_step,
              step_longer_by_under_a_tenth_of_the_pause, step_longer_by_half_the_pause,
              pause_between_two_steps]


@pytest.mark.parametrize("case", RULE_CASES, ids=lambda f: f.__name__)
def test_a_step_is_left_out_only_with_a_pause_beside_it_that_it_paid_for(case):
    m, recorded, expected = case()
    got = report(m, recorded)
    said = got["said"]
    assert said["steps_excluded"] == expected and said["pauses_over_cap"] is False
    assert said["train_tok_s_chip_all_steps"] == old_formula(m)
    lengths = [(m["data_wait_ms"][i] + m["step_ms"][i] + m["report_ms"][i]) / 1e3
               for i in range(m["steps"])]
    assert said["excluded_s"] == pytest.approx(sum(lengths[i] for i in expected), abs=1e-12)
    # the identity the earlier line and the last line keep
    assert got["train_tok_s_chip"] == (m["steps"] - len(expected)) * TOKENS / (
        m["window_s"] - said["excluded_s"]) / CHIPS
    if expected:
        # back within 0.1% of a window of quiet steps, from 1.5-7% under it
        quiet_rate = old_formula(window([statistics.median(m["step_ms"])] * m["steps"],
                                        wait_ms=m["data_wait_ms"][0],
                                        report_ms=m["report_ms"][0]))
        assert got["train_tok_s_chip"] == pytest.approx(quiet_rate, rel=1e-3)
        assert said["train_tok_s_chip_all_steps"] < 0.985 * quiet_rate
        assert said["slowest_steps"][0][0] in expected
    else:
        assert got["train_tok_s_chip"] == old_formula(m)  # as a float, to the last digit
    assert [p[1] for p in said["pauses"]] == pytest.approx(
        [pauses.length_s(p) for p in recorded])


def test_with_no_pause_the_value_is_the_old_formulas_float_and_the_means_are_all_steps():
    m = window(quiet(109))
    m["data_wait_ms"][3], m["report_ms"][5] = 200.0, 9.0
    m["window_s"] += 0.2089  # the steps no longer add up to it: a float like a run's
    got = report(m, [])
    assert got["train_tok_s_chip"] == old_formula(m) == got["said"]["train_tok_s_chip_all_steps"]
    assert got["said"]["excluded_s"] == 0.0 and got["said"]["steps_excluded"] == []
    assert got["data_wait_ms"] == sum(m["data_wait_ms"]) / 109
    assert got["report_ms"] == sum(m["report_ms"]) / 109


def test_the_timers_means_leave_out_the_steps_the_rate_leaves_out():
    """The ledger's PR 29 line of train-4k: a data wait of 2.3257 ms beside
    0.3478, one pause inside one ``next(batches)``."""
    steps = quiet(62, ms=816.5)
    m = window(steps, wait_ms=0.3478)
    m["data_wait_ms"][20] += 1230.0
    m["window_s"] += 1.23
    m["step_t_a"][21:] = [t + 1.23 for t in m["step_t_a"][21:]]
    got = report(m, [pause_in(m, 20, 1.2, offset_s=0.0001)])
    assert got["said"]["steps_excluded"] == [20]
    assert got["data_wait_ms"] == pytest.approx(0.3478)
    assert sum(m["data_wait_ms"]) / 62 > 20.0


def test_pauses_of_a_watcher_on_another_clock_are_not_used():
    m, recorded, _ = pr29_step_56()
    got = report(m, recorded, clock="another-host")
    assert got["said"]["steps_excluded"] == [] and got["said"]["watcher"]["clock_shared"] is False
    assert got["train_tok_s_chip"] == old_formula(m)
    assert len(got["said"]["pauses"]) == 1  # still shown


def test_overshoots_under_the_threshold_are_shown_and_leave_nothing_out():
    steps = quiet(109)
    steps[33] += 110.0
    m = window(steps)
    t = m["step_t_a"][33] + 0.2
    got = report(m, [], short=[[t, t + pauses.SLEEP_S + 0.11], [t - 900, t - 899.9]])
    assert got["said"]["short_overshoots"] == [[pytest.approx(t - 5000.0), pytest.approx(0.11)]]
    assert got["said"]["steps_excluded"] == [] and got["train_tok_s_chip"] == old_formula(m)


def test_pauses_before_the_window_are_reported_as_set_ups():
    m = window(quiet(109))
    t_w0 = m["t_window_start_mono"]
    got = report(m, [[t_w0 - 20.0, t_w0 - 18.5], [t_w0 + 60.0, t_w0 + 61.5]])
    assert got["said"]["pauses_in_setup"] == [[-20.0, pytest.approx(1.45)]]
    assert got["said"]["pauses"] == [[60.0, pytest.approx(1.45)]]
    assert got["said"]["steps_excluded"] == []


def hit_window(n_hit, seconds_each, n=109):
    steps = quiet(n)
    hit = list(range(10, 10 + 2 * n_hit, 2))
    for i in hit:
        steps[i] += seconds_each * 1e3
    m = window(steps)
    return m, [pause_in(m, i, seconds_each) for i in hit], hit


@pytest.mark.parametrize("n_hit,each,seconds,over", [
    (4, 1.5, 50.0, False),   # 7.84 s of 56.06: 14.0%
    (5, 1.5, 50.0, True),    # 9.80 s of 57.56: 17.0%
    (1, 8.0, 50.0, False),   # 8.46 s of 58.06: 14.6%
    (1, 9.5, 50.0, True),    # 9.96 s of 59.56: 16.7%
    # 3.92 s of 53.06 leave 49.14 s. A window lasts --seconds or longer, so
    # under the first cap this one never binds; asked for more seconds than
    # the window has, it does
    (2, 1.5, 61.0, False),   # 0.8 x 61 = 48.8
    (2, 1.5, 62.0, True),    # 0.8 x 62 = 49.6
])
def test_past_the_cap_nothing_is_left_out(n_hit, each, seconds, over):
    m, recorded, hit = hit_window(n_hit, each)
    got = report(m, recorded, seconds=seconds)
    said = got["said"]
    assert said["pauses_over_cap"] is over
    if over:
        assert said["steps_excluded"] == [] and said["excluded_s"] == 0.0
        assert got["train_tok_s_chip"] == old_formula(m)
    else:
        assert said["steps_excluded"] == hit
        assert said["excluded_s"] <= pauses.CAP_SHARE * m["window_s"]
        assert m["window_s"] - said["excluded_s"] >= pauses.CAP_LEFT * seconds
        assert got["train_tok_s_chip"] > 1.02 * old_formula(m)


def test_monotonic_is_one_clock_for_every_process_of_the_host():
    """What lets the watcher's stamps be compared with the loop's: a read in
    another process lies between two reads here."""
    before = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-I", "-S", "-c",
         "import time; print(repr(time.monotonic())); print(time.get_clock_info('monotonic'))"],
        capture_output=True, text=True, timeout=60, check=True).stdout.splitlines()
    after = time.monotonic()
    assert before < float(out[0]) < after and after - before < 60
    assert "CLOCK_MONOTONIC" in out[1]
    assert pauses.clock_id() and pauses.clock_id() == pauses.clock_id()


def test_the_watcher_can_import_nothing_but_the_standard_library():
    out = subprocess.run(
        [sys.executable, "-I", "-S", "-c",
         "import runpy, sys; runpy.run_path(sys.argv[1]); "
         "print(sorted(m for m in sys.modules if m.split('.')[0] in "
         "('jax', 'jaxlib', 'ray_tpu', 'numpy', 'benchmark')))\n"
         "try:\n import jax\nexcept ImportError: print('no jax')",
         pauses.__file__], capture_output=True, text=True, timeout=60, check=True).stdout
    assert out.split("\n")[:2] == ["[]", "no jax"]


def test_the_watcher_records_a_stop_as_one_pause_and_a_quiet_second_as_none():
    watcher = pauses.Watcher()
    try:
        assert watcher.problem is None
        time.sleep(1.0)  # started, and a quiet second
        t_stop = time.monotonic()
        os.kill(watcher.proc.pid, signal.SIGSTOP)
        time.sleep(0.6)
        os.kill(watcher.proc.pid, signal.SIGCONT)
        t_cont = time.monotonic()
        time.sleep(0.5)
        os.kill(watcher.proc.pid, signal.SIGSTOP)
        time.sleep(0.12)  # under the threshold: shown as short, no pause
        os.kill(watcher.proc.pid, signal.SIGCONT)
        time.sleep(0.5)
    finally:
        pid = watcher.proc.pid
        seen = watcher.stop()
    assert "problem" not in seen and seen["clock_id"] == pauses.clock_id()
    assert seen["started"] < t_stop < t_cont < seen["stopped"]
    assert len(seen["pauses"]) == 1, seen
    assert any(0.06 <= pauses.length_s(p) <= 0.25 for p in seen["short"]), seen
    start, end = seen["pauses"][0]
    assert start <= t_stop and t_cont <= end  # its stamps bracket this process's reads
    assert 0.55 <= pauses.length_s(seen["pauses"][0]) <= 0.9
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)  # ended and collected
    assert watcher.stop()["pauses"] == []  # a second stop is harmless


def test_a_watcher_ends_when_the_process_that_started_it_is_gone():
    code = ("import sys, time; sys.path.insert(0, sys.argv[1]); "
            "from benchmark import pauses; w = pauses.Watcher(); "
            "print(w.proc.pid, flush=True); time.sleep(0.5)")
    out = subprocess.run([sys.executable, "-c", code, ROOT], capture_output=True,
                         text=True, timeout=60, check=True)
    pid = int(out.stdout)
    deadline = time.monotonic() + 10
    while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not os.path.exists(f"/proc/{pid}")


def test_a_watcher_that_cannot_start_or_dies_costs_a_run_nothing(monkeypatch):
    monkeypatch.setattr(sys, "executable", "/no/such/python")
    seen = pauses.Watcher().stop()
    assert seen["pauses"] == [] and "not started" in seen["problem"]
    monkeypatch.undo()
    watcher = pauses.Watcher()
    time.sleep(0.3)
    watcher.proc.kill()
    seen = watcher.stop()
    assert seen["pauses"] == [] and "ended early" in seen["problem"]
    m = window(quiet(20))
    assert report(m, seen["pauses"], clock=seen["clock_id"])["train_tok_s_chip"] == old_formula(m)


def test_a_rehearsal_stopped_inside_its_window_keeps_the_identity(tmp_path):
    """Every process of a run (runner, raylet, workers, watcher) is stopped
    for 0.9 s every 6 s, so once or twice inside the 7 s window (and in
    set-up before it). Once is left out; twice is past the cap and nothing
    is. Either way the earlier line's fields and the last line's
    ``train_tok_s_chip`` keep the identity
    ``(steps - excluded) x tokens / (window_s - excluded_s) / chips``.
    (The rehearsal's seeded rows last 256 steps of ~33 ms: no longer window.)"""
    cell = Manifest().doc["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    with open(tmp_path / "stderr", "w+") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
             str(2**31 + 30), "--seconds", "7", "--trace", "0", "--rehearse"],
            cwd=ROOT, env=env, text=True, start_new_session=True,
            stdout=subprocess.PIPE, stderr=err)
        try:
            deadline = time.monotonic() + 400
            next_stop = time.monotonic() + 5.0
            while proc.poll() is None and time.monotonic() < deadline:
                if time.monotonic() >= next_stop:
                    next_stop += 6.0
                    os.killpg(proc.pid, signal.SIGSTOP)
                    time.sleep(0.9)
                    os.killpg(proc.pid, signal.SIGCONT)
                time.sleep(0.05)
            out, _ = proc.communicate(timeout=30)
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)  # whatever is left of it
            except ProcessLookupError:
                pass
        err.seek(0)
        assert proc.returncode == 0, out[-2000:] + err.read()[-3000:]
    lines = [json.loads(line) for line in out.splitlines()]
    last, said = lines[-1], next(x for x in lines if "steps_excluded" in x)
    declared = Manifest().cell(cell).declared(False)
    assert result.check(last, declared, trace=False, chips=None, platform="cpu") == []
    assert last["correct"] is True
    with open(os.path.join(ROOT, "benchmark", "rehearse.json")) as f:
        rehearse = json.load(f)
    tokens = rehearse["train"]["batch"] * rehearse["train_seq"]
    assert said["pauses"] and said["pauses_in_setup"], said
    assert all(0.5 <= p[1] <= 2.0 for p in said["pauses"] + said["pauses_in_setup"]), said
    assert said["watcher"] == {"clock_shared": True, "problem": None,
                               "watched_s": pytest.approx(20, abs=19)}
    value = last["metrics"]["train_tok_s_chip"]["value"]
    assert value == (said["steps"] - len(said["steps_excluded"])) * tokens / (
        said["window_s"] - said["excluded_s"]) / 1
    assert said["train_tok_s_chip_all_steps"] == said["steps"] * tokens / said["window_s"]
    # stops that began and ended inside the window: each made one step long
    inside = [p for p in said["pauses"]
              if p[0] > 0 and p[0] + p[1] + pauses.SLEEP_S < said["window_s"]]
    if said["pauses_over_cap"]:
        assert len(inside) >= 2
        assert said["steps_excluded"] == [] and value == said["train_tok_s_chip_all_steps"]
    else:
        assert len(said["steps_excluded"]) >= len(inside)
        assert said["excluded_s"] >= 0.5 * len(said["steps_excluded"])
        assert (value > said["train_tok_s_chip_all_steps"]) == bool(said["steps_excluded"])
