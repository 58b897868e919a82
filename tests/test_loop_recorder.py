"""Tick-level stall attribution + per-request flight recorder (ISSUE 18).

Bounds tests for ``ray_tpu.observability.loop_recorder``: the stall ring
and request timeline are fixed-size, allocation-free on the hot path,
keep the newest-N with an ``overflowed`` flag when lapped, and the
engine dumps a breached request's timeline exactly once.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.observability import loop_recorder
from ray_tpu.observability.loop_recorder import RequestTimeline, StallRing


def test_stall_ring_overflow_keeps_newest():
    ring = StallRing(capacity=8)
    assert not ring.overflowed
    for i in range(20):
        ring.record(float(i), 2.0 * i, 0.5)
    assert ring.ticks == 20
    assert ring.overflowed
    # drain caps at capacity and returns the NEWEST-N splits in order
    rows = ring.drain()
    assert len(rows) == 8
    assert [r[0] for r in rows] == [float(i) for i in range(12, 20)]
    # totals cover the full lifetime, not just the surviving window
    assert ring.totals_ms[loop_recorder.WAIT_UP] == sum(range(20))
    snap = ring.snapshot()
    assert snap["ticks"] == 20 and snap["overflowed"]
    assert abs(sum(snap["frac"].values()) - 1.0) < 0.01


def test_stall_ring_drain_is_incremental():
    ring = StallRing(capacity=16)
    for _ in range(5):
        ring.record(0.1, 0.8, 0.1)
    assert len(ring.drain()) == 5
    assert ring.drain() == []  # nothing new since the last flush
    for _ in range(3):
        ring.record(0.2, 0.7, 0.1)
    assert len(ring.drain()) == 3


def test_classify_stage_and_loop():
    compute = {"wait_up": 0.1, "compute": 0.8, "wait_down": 0.1}
    starved = {"wait_up": 0.7, "compute": 0.2, "wait_down": 0.1}
    backed = {"wait_up": 0.1, "compute": 0.2, "wait_down": 0.7}
    assert loop_recorder.classify_stage(compute, ticks=10) == "compute_bound"
    assert loop_recorder.classify_stage(starved, ticks=10) == "starved"
    assert loop_recorder.classify_stage(backed, ticks=10) == "backpressured"
    assert loop_recorder.classify_stage(None, ticks=0) == "idle"
    assert loop_recorder.classify_loop({
        "a": {"ticks": 10, "frac": starved},
        "b": {"ticks": 10, "frac": compute},
        "idle": {"ticks": 0, "frac": compute},
    }) == "b"


def test_stall_ring_registry_bounded():
    before = len(loop_recorder._rings)
    r1 = loop_recorder.get_stall_ring("loop-x", "s0", capacity=4)
    assert loop_recorder.get_stall_ring("loop-x", "s0") is r1
    r1.record(0.0, 1.0, 0.0)
    assert loop_recorder.get_stall_ring("loop-x", "s0").snapshot()["ticks"] == 1
    # the registry never grows without bound (LRU-drops the oldest key)
    for i in range(loop_recorder._RINGS_MAX + 8):
        loop_recorder.get_stall_ring(f"loop-fill-{i}", "s")
    assert len(loop_recorder._rings) <= loop_recorder._RINGS_MAX
    assert before <= loop_recorder._RINGS_MAX


def test_request_timeline_overflow_keeps_newest_and_pins():
    tl = RequestTimeline(capacity=16)
    tl.add(loop_recorder.EV_ADMIT, 5, now=1.0)
    tl.add(loop_recorder.EV_PREFIX_HIT, 3, now=1.1)
    tl.add(loop_recorder.EV_FIRST_TOKEN, 5, now=1.2)
    for i in range(40):  # lap the ring with per-token events
        tl.add(loop_recorder.EV_TOKEN, i + 1, now=2.0 + i * 0.01)
    tl.add(loop_recorder.EV_RETIRE, 40, now=3.0)
    assert tl.overflowed
    payload = tl.to_payload()
    assert payload["overflowed"] and payload["n_events"] == 44
    assert payload["dropped"] == 44 - 16
    evs = payload["events"]
    # lapped pinned events are re-prepended so the story still opens at
    # admission; the tail keeps the newest events including the terminal
    names = [e["ev"] for e in evs]
    assert names[0] == "admit" and evs[0]["pinned"]
    assert "prefix_hit" in names[:3] and "first_token" in names[:3]
    assert names[-1] == "retire"
    # surviving window is newest-N: the last pre-retire token is present
    assert any(e["ev"] == "token" and e["v"] == 40 for e in evs)
    assert payload["start"] == 1.0 and payload["end"] == 3.0


def test_request_timeline_byte_budget_at_1k_requests():
    """1k concurrent always-on recorders stay within a ~1 MiB budget —
    the 'hundreds of bytes per request' claim, enforced."""
    timelines = [RequestTimeline() for _ in range(1000)]
    per = timelines[0].nbytes()
    assert per <= 1024, per  # each recorder: under 1 KiB of array storage
    assert sum(t.nbytes() for t in timelines) <= 1 << 20


def test_request_timeline_value_clamp_and_pin_cap():
    tl = RequestTimeline(capacity=8)
    tl.add(loop_recorder.EV_ADMIT, 2**40)  # out-of-range value clamps to 0
    assert tl.events()[0]["v"] == 0
    for _ in range(20):  # pinned mirror is capped, never grows unbounded
        tl.add(loop_recorder.EV_PREFIX_HIT, 1)
    assert len(tl._pinned) <= 8


@pytest.fixture(scope="module")
def small_model():
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_dumps_timeline_once_per_request(small_model):
    from ray_tpu.llm.engine import InferenceEngine, Request

    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64)
    req = Request("dump-once", [1, 5, 9], max_new_tokens=4)
    eng.add_request(req)
    while not req.done:
        eng.step()
    assert eng.dump_timeline(req, "test_breach") is True
    assert eng.dump_timeline(req, "test_breach") is False  # dump-once
    assert eng.metrics["timeline_dumps"] == 1
    rows = eng.breach_samples()
    assert len(rows) == 1 and rows[0]["request_id"] == "dump-once"
    assert rows[0]["reason"] == "test_breach"


def test_deadline_breach_yields_complete_timeline_via_cli(
        small_model, ray_cluster, capsys):
    """Acceptance: an injected deadline breach dumps a COMPLETE
    ``llm.request_timeline`` span — admission through expiry — and
    ``cli trace --request <id>`` retrieves it."""
    from ray_tpu.cli import main
    from ray_tpu.llm.engine import InferenceEngine, Request
    from ray_tpu.util import state

    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64)
    req = Request("breach-req", [2, 4, 6, 8], max_new_tokens=32,
                  deadline=time.time() + 0.25)
    eng.add_request(req)
    eng.step()            # admit + start prefill before the deadline hits
    time.sleep(0.3)       # injected stall pushes the request past it
    deadline = time.monotonic() + 10.0
    while not req.done and time.monotonic() < deadline:
        eng.step()
    assert req.finish_reason == "deadline"
    assert eng.metrics["timeline_dumps"] >= 1

    # connected engines route spans through the worker's task-event
    # flusher (~5s cadence); standalone ones land in the local buffer —
    # find_request_timeline checks both, so just poll.
    span, poll_deadline = None, time.monotonic() + 30.0
    while span is None and time.monotonic() < poll_deadline:
        span = state.find_request_timeline("breach-req")
        if span is None:
            time.sleep(0.5)
    assert span is not None, "llm.request_timeline dump never surfaced"
    names = [e["ev"] for e in span["attrs"]["events"]]
    assert names[0] == "admit"                # complete: opens at admission
    assert "deadline_expired" in names        # ... and records the expiry
    assert span["attrs"]["reason"] == "deadline"

    assert main(["trace", "--request", "breach-req"]) == 0
    out = capsys.readouterr().out
    assert "admit" in out and "deadline_expired" in out
    assert "breach-req" in out
    # unknown request id: non-zero exit, no traceback
    assert main(["trace", "--request", "no-such-request"]) != 0


def test_engine_shed_dumps_timeline(small_model):
    from ray_tpu.llm.engine import InferenceEngine, QueueFullError, Request

    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=1, max_len=64,
                          max_queued_requests=1)
    eng.add_request(Request("q0", [1, 2, 3], max_new_tokens=4))
    before = eng.metrics["timeline_dumps"]
    with pytest.raises(QueueFullError):
        eng.add_request(Request("shed-me", [1, 2, 3], max_new_tokens=4))
    assert eng.metrics["timeline_dumps"] == before + 1
    rows = [r for r in eng.breach_samples() if r["request_id"] == "shed-me"]
    assert rows and rows[0]["reason"] == "shed_queue_full"
