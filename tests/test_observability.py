"""Observability: task events → state API + timeline; metrics; CLI;
distributed tracing (span propagation, LEASED transitions, TTFT and
lease-stage histograms).

Mirrors the reference's state-API tests (``python/ray/tests/test_state_api*``)
and ``ray.timeline`` (``_private/state.py:965``).
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.observability import tracing
from ray_tpu.util import state


def _poll(fn, timeout=30.0, interval=0.3):
    """Poll fn() until it returns a truthy value (task-event/metric
    flushers run on ~1-5s intervals); returns the last value."""
    deadline = time.monotonic() + timeout
    value = fn()
    while not value and time.monotonic() < deadline:
        time.sleep(interval)
        value = fn()
    return value


@pytest.fixture(autouse=True)
def _cluster(ray_cluster):
    yield


def test_task_events_reach_state_api():
    @ray_tpu.remote
    def traced_task(x):
        return x * 2

    assert ray_tpu.get(traced_task.remote(21), timeout=60) == 42
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        tasks = [t for t in state.list_tasks() if t["name"] == "traced_task"]
        # Owner (SUBMITTED/terminal) and executor (RUNNING/FINISHED)
        # events ride two DIFFERENT processes' flush cadences: poll until
        # the record is COMPLETE, not merely terminal — breaking on the
        # executor's FINISHED alone raced the owner's flush by up to one
        # interval (pre-existing flake, seen whenever the phase aligned).
        if (tasks and tasks[-1]["state"] == "FINISHED"
                and "SUBMITTED" in tasks[-1]["events"]):
            break
        time.sleep(0.3)
    assert tasks, "task events never reached the GCS"
    t = tasks[-1]
    assert t["state"] == "FINISHED"
    assert "SUBMITTED" in t["events"] and "FINISHED" in t["events"]


def test_failed_task_recorded():
    @ray_tpu.remote
    def exploder():
        raise ValueError("recorded")

    with pytest.raises(ValueError):
        ray_tpu.get(exploder.remote(), timeout=60)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        tasks = [t for t in state.list_tasks() if t["name"] == "exploder"]
        if tasks and tasks[-1]["state"] == "FAILED":
            break
        time.sleep(0.3)
    assert tasks and tasks[-1]["state"] == "FAILED"
    assert "recorded" in tasks[-1]["error"]


def test_timeline_dump(tmp_path):
    @ray_tpu.remote
    def timed():
        time.sleep(0.05)
        return 1

    ray_tpu.get([timed.remote() for _ in range(3)], timeout=60)
    time.sleep(1.5)  # let the flusher run
    path = ray_tpu.timeline(str(tmp_path / "trace.json"))
    trace = json.load(open(path))
    assert isinstance(trace, list) and trace
    timed_events = [e for e in trace if e["name"] == "timed"]
    assert len(timed_events) >= 3
    for e in timed_events:
        assert e["ph"] == "X" and e["dur"] > 0 and "pid" in e and "tid" in e


def test_state_api_nodes_workers_objects():
    nodes = state.list_nodes()
    assert any(n["state"] == "ALIVE" for n in nodes)
    workers = state.list_workers()
    assert workers, "no workers listed"
    import numpy as np

    ref = ray_tpu.put(np.zeros(200_000, dtype=np.float32))
    objs = state.list_objects()
    assert any(o["state"] == "SEALED" for o in objs)
    del ref


def test_metrics_roundtrip():
    from ray_tpu.util.metrics import Counter, Gauge, get_metrics, prometheus_text

    c = Counter("test_requests_total", tag_keys=("kind",))
    c.inc(3, {"kind": "a"})
    g = Gauge("test_queue_len")
    g.set(7)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        metrics = {m["name"]: m for m in get_metrics()}
        if "test_requests_total" in metrics and "test_queue_len" in metrics:
            break
        time.sleep(0.5)
    assert metrics["test_requests_total"]["value"] == 3
    assert metrics["test_queue_len"]["value"] == 7
    text = prometheus_text(list(metrics.values()))
    assert 'test_requests_total{kind="a"} 3' in text


def test_cli_list_and_status(capsys):
    from ray_tpu.cli import main

    assert main(["list", "nodes"]) == 0
    out = capsys.readouterr().out
    assert "NODE_ID" in out
    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "alive" in out and "CPU" in out


def test_summarize_tasks():
    @ray_tpu.remote
    def summary_probe():
        return 1

    ray_tpu.get([summary_probe.remote() for _ in range(2)], timeout=60)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        summary = state.summarize_tasks()
        if summary.get("summary_probe", {}).get("FINISHED", 0) >= 2:
            break
        time.sleep(0.3)
    assert summary["summary_probe"]["FINISHED"] >= 2


def test_leased_transition_recorded():
    """Remote tasks pass through LEASED between SUBMITTED and RUNNING
    (ROADMAP 1c: lease-stage timestamps for the cascade investigation)."""

    @ray_tpu.remote
    def leased_probe():
        return 1

    assert ray_tpu.get(leased_probe.remote(), timeout=60) == 1

    def _find():
        # the owner, the raylet and the worker each flush their events on
        # their own timer: wait until all three stamps have arrived
        tasks = [t for t in state.list_tasks() if t["name"] == "leased_probe"
                 and t["state"] == "FINISHED"
                 and {"SUBMITTED", "LEASED", "FINISHED"} <= set(t["events"])]
        return tasks

    tasks = _poll(_find)
    assert tasks, "no finished leased_probe task with a LEASED event"
    events = tasks[-1]["events"]
    assert events["SUBMITTED"] <= events["LEASED"] <= events["FINISHED"]


def test_task_span_propagation():
    """submit → lease → execute → get hops share one trace and form a
    connected parent/child tree."""

    @ray_tpu.remote
    def traced_child(x):
        return x + 1

    with tracing.span("test-root", kind="test") as ctx:
        assert ray_tpu.get(traced_child.remote(1), timeout=60) == 2
    trace_id = ctx.trace_id

    def _spans():
        spans = state.list_spans(trace_id=trace_id)
        names = {s["name"] for s in spans}
        if ("test-root" in names
                and "task traced_child" in names
                and "execute traced_child" in names
                and any(n.startswith("lease ") for n in names)):
            return spans
        return None

    spans = _poll(_spans)
    assert spans, f"incomplete span tree: {state.list_spans(trace_id=trace_id)}"
    by_id = {s["span_id"]: s for s in spans}
    task = next(s for s in spans if s["name"] == "task traced_child")
    execute = next(s for s in spans if s["name"] == "execute traced_child")
    root = next(s for s in spans if s["name"] == "test-root")
    assert execute["parent_id"] == task["span_id"]
    assert task["parent_id"] == root["span_id"]
    assert all(s["trace_id"] == trace_id for s in spans)
    # lease span (recorded by the raylet) parents onto the task span
    lease = next(s for s in spans if s["name"].startswith("lease "))
    assert by_id[lease["parent_id"]]["name"].startswith("task ")
    # ray.get inside the root context shows up as a child hop
    assert any(s["name"].startswith("get ") for s in spans)


def test_timeline_merges_spans(tmp_path):
    """Spans appear in the chrome trace as per-trace slices + flow links."""
    with tracing.span("timeline-span-probe", kind="test") as ctx:
        pass
    path = str(tmp_path / "trace_spans.json")

    def _dump():
        ray_tpu.timeline(path)
        trace = json.load(open(path))
        slices = [e for e in trace if e.get("cat") == "span"
                  and e.get("args", {}).get("trace_id") == ctx.trace_id]
        return slices

    slices = _poll(_dump)
    assert slices and slices[0]["ph"] == "X"
    assert slices[0]["pid"].startswith("trace:")


def test_lease_stage_histograms():
    """The GCS exports per-raylet lease-stage duration histograms fed by
    LEASED events (submit→lease, queue wait, spawn, lease→run)."""

    @ray_tpu.remote
    def stage_probe():
        return 1

    ray_tpu.get([stage_probe.remote() for _ in range(3)], timeout=60)

    def _rows():
        from ray_tpu.util.metrics import get_metrics

        rows = [m for m in get_metrics() if m["name"] == "ray_tpu_lease_stage_ms"]
        stages = {m["tags"].get("stage") for m in rows if m.get("count")}
        if {"lease_queue_wait", "worker_spawn"} <= stages:
            return rows
        return None

    rows = _poll(_rows)
    assert rows, "lease-stage histograms never populated"
    assert all(m["type"] == "histogram" for m in rows)


def test_serve_request_span_tree_and_ttft():
    """Acceptance: one traced serve request yields a connected span tree
    (proxy → router → replica task → engine prefill/decode) and a
    non-empty serve_ttft_ms histogram."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    try:
        serve.run(build_llm_app("debug-128", max_slots=4, max_len=128), name="llm")
        addr = serve.http_address()
        body = json.dumps({"prompt": "hello trace", "max_tokens": 6}).encode()
        req = urllib.request.Request(addr + "/v1/completions", data=body,
                                     headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=60)
        out = json.loads(resp.read())
        assert out["usage"]["completion_tokens"] == 6
        trace_id = resp.headers.get("x-raytpu-trace-id")
        assert trace_id, "proxy did not echo the trace id"

        def _spans():
            spans = state.list_spans(trace_id=trace_id)
            names = {s["name"] for s in spans}
            want_prefixes = ("http ", "router.queue ", "task ", "execute ")
            if all(any(n.startswith(p) for n in names) for p in want_prefixes) \
                    and {"llm.prefill", "llm.decode"} <= names:
                return spans
            return None

        spans = _poll(_spans)
        assert spans, (
            f"incomplete serve span tree: "
            f"{[s['name'] for s in state.list_spans(trace_id=trace_id)]}")
        # prefill's ancestry must reach the proxy's http root span
        by_id = {s["span_id"]: s for s in spans}
        hop = next(s for s in spans if s["name"] == "llm.prefill")
        seen = []
        while hop is not None:
            seen.append(hop["name"])
            hop = by_id.get(hop["parent_id"])
        assert any(n.startswith("http ") for n in seen), seen
        prefill = next(s for s in spans if s["name"] == "llm.prefill")
        assert prefill["attrs"]["prompt_tokens"] > 0

        def _ttft():
            from ray_tpu.util.metrics import get_metrics

            return [m for m in get_metrics()
                    if m["name"] == "serve_ttft_ms" and m.get("count", 0) > 0]

        rows = _poll(_ttft)
        assert rows, "serve_ttft_ms histogram never populated"
        assert rows[0]["tags"]["deployment"]  # tagged per deployment
        from ray_tpu.util.metrics import histogram_quantile

        assert histogram_quantile(rows[0], 0.5) is not None
    finally:
        serve.shutdown()


def test_cli_trace_and_timeline_smoke(tmp_path, capsys):
    """Tier-1 smoke for the CLI tracing surfaces against a live cluster:
    `cli timeline`, `cli trace` (list) and `cli trace <id>` (tree)."""
    from ray_tpu.cli import main

    @ray_tpu.remote
    def cli_probe():
        return 1

    with tracing.span("cli-smoke-root", kind="test") as ctx:
        assert ray_tpu.get(cli_probe.remote(), timeout=60) == 1

    def _ready():
        names = {s["name"] for s in state.list_spans(trace_id=ctx.trace_id)}
        return {"cli-smoke-root", "task cli_probe"} <= names

    assert _poll(_ready), "root/task spans never flushed"

    out_path = str(tmp_path / "cli_timeline.json")
    assert main(["timeline", "-o", out_path]) == 0
    assert json.load(open(out_path))
    capsys.readouterr()

    # the list is the newest ``--limit`` traces (20 unless told): every
    # long-poll retry of a neighbouring test's serve actors is a trace of its
    # own, and twenty of them arriving since ours pushed it off the list
    assert main(["trace", "--limit", "1000"]) == 0
    out = capsys.readouterr().out
    assert "TRACE_ID" in out and ctx.trace_id[:12] in out

    assert main(["trace", ctx.trace_id]) == 0
    out = capsys.readouterr().out
    assert "cli-smoke-root" in out and "task cli_probe" in out


def test_prometheus_help_type_and_quantile():
    from ray_tpu.util.metrics import (
        LATENCY_MS_BOUNDARIES, Histogram, histogram_quantile, prometheus_text)

    h = Histogram("obs_test_latency_ms", "A test latency histogram",
                  tag_keys=("kind",), register=False)
    assert h.boundaries == LATENCY_MS_BOUNDARIES  # ms-scale default
    for v in (3, 30, 300):
        h.observe(v, {"kind": "a"})
    snap = h.snapshot()[0]
    text = prometheus_text([snap])
    assert "# HELP obs_test_latency_ms A test latency histogram" in text
    assert "# TYPE obs_test_latency_ms histogram" in text
    assert 'obs_test_latency_ms_bucket{kind="a",le="+Inf"} 3' in text
    q = histogram_quantile(snap, 0.5)
    assert 2.0 <= q <= 100.0
    # counter/gauge families get TYPE lines too
    text = prometheus_text([
        {"name": "obs_test_total", "type": "counter", "desc": "c", "tags": {}, "value": 1}])
    assert "# TYPE obs_test_total counter" in text


def test_train_step_gauges():
    from ray_tpu.train.session import TrainContext, _Session
    from ray_tpu.util.metrics import snapshot_all

    ctx = TrainContext(world_rank=0, world_size=1, local_rank=0,
                       local_world_size=1, node_rank=0,
                       experiment_name="obs-test", storage_path="/tmp")
    session = _Session(ctx, None)
    session.report({"tokens_per_sec_per_chip": 1234.0, "mfu": 0.45})
    session.report({"tokens_per_sec_per_chip": 2345.0, "mfu": 0.5})
    snap = {(m["name"], m["tags"].get("experiment")): m for m in snapshot_all()}
    assert snap[("train_tokens_per_s", "obs-test")]["value"] == 2345.0
    assert snap[("train_mfu", "obs-test")]["value"] == 0.5
    assert snap[("train_step_time_s", "obs-test")]["value"] >= 0.0


def test_worker_logs_stream_to_driver(ray_cluster, capfd):
    """Worker prints surface on the driver's stderr with a worker/node
    prefix (reference log_monitor + print_logs)."""
    import time

    @ray_tpu.remote
    def speak():
        print("log-monitor-test-line")
        return True

    assert ray_tpu.get(speak.remote(), timeout=60)
    deadline = time.time() + 10
    seen = ""
    while time.time() < deadline:
        seen += capfd.readouterr().err
        if "log-monitor-test-line" in seen:
            break
        time.sleep(0.25)
    assert "log-monitor-test-line" in seen
    assert "node=" in seen.split("log-monitor-test-line")[0].rsplit("(", 1)[-1]


def test_dead_workers_last_words_are_forwarded_then_its_log_is_dropped(ray_cluster, capfd):
    """A worker that dies mid-line (no newline after its last words) will
    write no more: the tail is forwarded as it is, and once all it wrote
    is forwarded the raylet stops polling that file (a long-lived raylet
    with worker churn would otherwise stat every dead worker's log at
    every tick, for ever)."""
    import os
    import time

    from ray_tpu.core import api

    @ray_tpu.remote(max_retries=0)
    def last_words():
        os.write(1, b"a whole line\nlast-words-without-newline")
        os._exit(1)

    with pytest.raises(Exception):
        ray_tpu.get(last_words.remote(), timeout=60)
    raylet = api._node.raylet
    seen = ""
    deadline = time.time() + 20
    while time.time() < deadline:
        seen += capfd.readouterr().err
        live = {w.log_path for w in raylet._workers.values()}
        if "last-words-without-newline" in seen and set(raylet._log_offsets) <= live:
            break
        time.sleep(0.25)
    assert "last-words-without-newline" in seen
    assert set(raylet._log_offsets) <= {w.log_path for w in raylet._workers.values()}
