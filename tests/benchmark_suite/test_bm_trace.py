"""The reduction from a profiler trace to numbers, on a recorded trace kept
with the benchmark: lines of one plane cover the same time and the op line
nests, so busy time is a union and an op's time its self time."""

import json
import os

import pytest

from benchmark import layer_metrics, trace_reduce
from benchmark.manifest import HERE

FIXTURE = os.path.join(HERE, "testdata", "synthetic_tpu.xplane.pb")


def reader(metric):
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


READERS = {m: reader(m) for m in (
    "collective.time_share", "collective.exposed_share",
    "kernel.custom_call_share.train", "device.idle_share.train")}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(FIXTURE, trace_reduce.load_profile("tpu"),
                                     layer_metrics.union_specs(READERS))


def test_the_window_is_the_captures_own_and_busy_a_union_clipped_to_it(summary):
    # the capture's benchmark_capture event spans 0.5-9.75 ms: the window,
    # idle ends included. Device 0's ops cover 1-5, 5-5.5 and 7.5-9 ms (its
    # Steps, Modules and Ops lines together would sum to 22 ms); device 1
    # runs 1 ms later and its last op, 8.5-10 ms, is cut at 9.75
    assert summary["window_s"] == pytest.approx(0.00925)
    assert summary["busy_s_per_device"] == pytest.approx([0.006, 0.00575])
    assert summary["busy_s"] == pytest.approx(0.005875)
    assert summary["devices"] == 2


def test_an_op_that_spans_its_body_keeps_only_its_self_time(summary):
    assert summary["ops"]["while.1"][0] == pytest.approx(0.0005)
    assert summary["ops"]["custom-call.2"] == [pytest.approx(0.0019), 1]
    assert sum(v[0] for v in summary["ops"].values()) == pytest.approx(0.006)
    assert summary["device_ops"][0][0] == "custom-call.2"
    assert len(summary["device_ops"]) <= 10


def test_idle_gaps_go_to_what_the_host_was_doing(summary):
    gaps = dict(summary["idle_gaps"])
    assert gaps["$session.py:88 report"] == pytest.approx(0.002)        # 5.5-7.5 ms
    # the window's idle ends, 0.5-1 and 9-9.75 ms; the capture's own event
    # covers them too and is never what a gap is attributed to
    assert gaps["$train.py:120 timed_step"] == pytest.approx(0.00125)
    assert sum(gaps.values()) == pytest.approx(0.00925 - 0.006)


def test_a_trace_needs_exactly_one_window_event():
    profile = {**trace_reduce.load_profile("tpu"), "window_event": "^no_such_event$"}
    with pytest.raises(ValueError, match="0 events match"):
        trace_reduce.reduce_trace(FIXTURE, profile)


def test_an_async_collective_counts_from_its_start_to_the_end_of_its_done():
    evs = [("%async-collective-start.7 = x", 2.0, 2.1), ("%fusion.1 = y", 2.1, 4.0),
           ("%async-collective-done.7 = x", 4.0, 4.5),
           ("%async-collective-start = x", 4.5, 4.6), ("%async-collective-done = x", 4.6, 4.7),
           ("%async-collective-done.9 = x", 5.0, 5.1),          # no start seen: left out
           ("%all-reduce.3 = f32[8] all-reduce(f32[8] %fusion.1)", 6.0, 7.0),
           ("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.3)", 7.0, 8.0)]
    got = trace_reduce.inflight(evs, READERS["collective.time_share"]["params"])
    assert sorted(got) == [(2.0, 4.5), (4.5, 4.7), (6.0, 7.0)]


def test_union_clips_to_the_window_and_never_exceeds_it():
    total, gaps = trace_reduce.union_seconds(
        [(-5.0, 1.0), (0.5, 2.0), (0.7, 0.9), (3.0, 30.0)], 0.0, 10.0)
    assert total == pytest.approx(9.0)
    assert gaps == [(2.0, 3.0)]
    assert trace_reduce.union_seconds([], 0.0, 1.0) == (0.0, [(0.0, 1.0)])


def test_a_trace_without_the_named_plane_is_an_error():
    with pytest.raises(ValueError, match="no plane matching"):
        trace_reduce.reduce_trace(
            FIXTURE, {**trace_reduce.load_profile("tpu"), "device_plane": "^/device:GPU"})


def test_layer_metric_readers_on_the_recorded_trace(summary):
    obs = {"trace": summary, "timers": {"step_ms_median": 870.0},
           "train": {"tok_s_chip": 9000.0, "flops_per_token": 1e10,
                     "peak_flops_per_s": 2e14}}
    readers = dict(READERS, **{
        "step": {"kind": "value", "params": {"path": "timers.step_ms_median"}},
        "mfu": {"kind": "ratio", "params": {
            "num": ["train.tok_s_chip", "train.flops_per_token"],
            "den": ["train.peak_flops_per_s"], "scale": 100.0}},
        "nothing": {"kind": "ratio", "params": {"num": [1], "den": [0]}}})
    got = layer_metrics.read_all(readers, obs)
    # in flight: the pair 2.4-4.5 ms and the all-gather 5-5.5 ms; exposed:
    # the start and done fusions (0.1 ms each) and the all-gather
    assert got["collective.time_share"] == pytest.approx(100 * 2.6 / 9.25)
    assert got["collective.exposed_share"] == pytest.approx(100 * 0.7 / 9.25)
    assert got["kernel.custom_call_share.train"] == 0.0   # no Mosaic call in the fixture
    assert got["device.idle_share.train"] == pytest.approx(100 * (1 - 5.875 / 9.25))
    assert got["step"] == 870.0 and got["mfu"] == pytest.approx(45.0)
    assert "nothing" not in got                            # nothing to read: left out


def test_a_tpu_op_is_named_by_its_hlo_text_and_shortened_for_the_breakdown():
    text = ('%closed_call.19 = bf16[12,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(s32[12,68]{1,0:T(8,128)S(1)} %get-tuple-element.2074), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace_reduce.short_name(text) == "%closed_call.19 custom-call tpu_custom_call"
    assert trace_reduce.short_name(
        "%while.15 = (s32[]{:T(128)}, bf16[2,4096]{1,0:T(8,128)(2,1)}) while(%tuple.1)"
    ) == "%while.15 while"
    assert trace_reduce.short_name("fusion.1") == "fusion.1"
    # a collective is told by the op's own name, not by an operand's
    parts = {text: [0.5, 1],
             "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %all-gather-done.3)": [0.25, 1],
             "%all-gather-start.3 = bf16[8]{0} all-gather-start(bf16[2]{0} %p)": [0.125, 2]}
    assert trace_reduce.matching(
        parts, READERS["collective.exposed_share"]["params"]["pattern"]) == (0.125, 2)
    assert trace_reduce.matching(
        parts, 'custom_call_target="tpu_custom_call"') == (0.5, 1)
