"""One clock: the program's host spans (``tracing.annotate``) on the
profiler's trace, the capture's own window, the reduction of a capture
(``observability/profile.py``) with its table by scope, what a kernel costs,
and the engine's always-on step counters. What ``tracing.device_scope`` puts
into an op's own text: ``tests/test_device_scopes.py``."""

import contextlib
import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xplane_writer
from ray_tpu.observability import profile, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _captured(work, tmp_path) -> str:
    """Run ``work()`` inside a capture of this process, taken as
    ``profile.capture`` takes one (no Python frames: hooking every thread of
    a process that holds a cluster costs the export tens of seconds); the
    trace's path."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(profile.WINDOW, wall_s=time.time(),
                                          mono_s=time.monotonic()):
            work()
    finally:
        jax.profiler.stop_trace()
    return str(tmp_path)


def _events(path: str) -> list:
    """(name, start_s, end_s, stats) of every host event of a capture."""
    from jax.profiler import ProfileData

    pb = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)[0]
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
            for plane in ProfileData.from_file(pb).planes
            for line in plane.lines for e in line.events
            if e.name == profile.WINDOW
            or not e.name.startswith("$") and tracing.MARK in dict(e.stats)]


def _inside(events, inner: str, outer: str) -> bool:
    outers = [(s, e) for n, s, e, _ in events if n == outer]
    inners = [(s, e) for n, s, e, _ in events if n == inner]
    return bool(inners) and all(any(os_ <= s and e <= oe for os_, oe in outers)
                                for s, e in inners)


@contextlib.contextmanager
def _recorded_spans():
    """The wall-clock spans recorded meanwhile, wherever they would have
    gone (a connected core worker sends them to the GCS)."""
    spans, real = [], tracing.record_span
    tracing.record_span = spans.append
    try:
        yield spans
    finally:
        tracing.record_span = real


# ------------------------------------------------------------- annotate
def test_annotate_is_a_noop_that_imports_nothing_without_jax():
    code = ("import sys\n"
            "from ray_tpu.observability import tracing\n"
            "assert 'jax' not in sys.modules, 'importing tracing imported jax'\n"
            "with tracing.annotate('data.next_batch', rows=1) as span:\n"
            "    span.set_metadata(bytes=2)\n"
            "with tracing.span('get x1'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


@pytest.mark.parametrize("name,how", [("router.pick", "annotate"), ("get x1", "span")])
def test_a_span_of_any_layer_is_a_program_span(tmp_path, name, how):
    """``summarize`` keys on the mark ``annotate`` leaves, not on a list of
    layers: a site in a new layer, or a Dapper span of any name, is in the
    table and owns the gaps under it."""
    def work():
        with (tracing.annotate(name, n=1) if how == "annotate" else tracing.span(name)):
            float(jnp.ones(8).sum())

    summary = profile.summarize(_captured(work, tmp_path))
    row = {s["name"]: s for s in summary["spans"]}[name]
    assert row["count"] == 1 and row["total_ms"] > 0
    assert all("." not in s["name"] or not s["name"].rsplit(".", 1)[1].isdigit()
               for s in summary["spans"]), summary["spans"]  # no dot_general.56


def test_tracing_enabled_is_read_once_per_process(monkeypatch):
    from ray_tpu.core import config

    monkeypatch.setattr(tracing, "_enabled", None)
    calls = []
    real = config.get_config
    monkeypatch.setattr(config, "get_config", lambda: calls.append(1) or real())
    for _ in range(5):
        tracing.record_span(tracing.make_span("x", "app", 0.0, 1.0, "t" * 32))
    assert len(calls) == 1


# ------------------------------------------------- a two-step train loop
@pytest.fixture(scope="module")
def train_capture(tmp_path_factory):
    """Two steps of a train loop in this process: batches from a split
    Data stream, a device_put, train.report with a checkpoint directory
    and with async state."""
    import ray_tpu
    from ray_tpu import data
    from ray_tpu.resilience.checkpoint import AsyncCheckpointManager
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.session import TrainContext, _Session

    tmp = tmp_path_factory.mktemp("train")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    rows = np.arange(8 * 16, dtype=np.int32).reshape(8, 16)
    shard = data.from_numpy(rows, column="tokens").streaming_split(1)[0]
    manager = AsyncCheckpointManager(str(tmp / "async"), run_name="", keep_k=1)
    session = _Session(TrainContext(0, 1, 0, 1, 0, "exp", str(tmp / "store")),
                       None, async_ckpt=manager)
    (tmp / "ckpt").mkdir()
    (tmp / "ckpt" / "w.txt").write_text("w")

    def work():
        batches = shard.to_device_batches(batch_size=4)
        for step in range(2):
            batch = next(batches)
            loss = float(jnp.sum(batch["tokens"]))
            session.report({"step": step, "loss": loss},
                           checkpoint=Checkpoint(str(tmp / "ckpt")),
                           state={"w": jnp.ones(4)})
        # the commit's span has to END inside the capture: on a loaded
        # host the write has taken longer than 10 s and did arrive
        assert manager.wait(120.0), "the async commit never finished"

    try:
        path = _captured(work, tmp / "trace")
    finally:
        manager.close()
    return _events(path)


@pytest.mark.parametrize("name", [
    "data.next_batch", "data.device_put", "train.report",
    "train.ckpt.snapshot", "train.ckpt.commit"])
def test_train_loop_span_is_on_the_host_plane(train_capture, name):
    assert name in {n for n, *_ in train_capture}


def test_train_spans_nest_and_carry_what_was_processed(train_capture):
    assert _inside(train_capture, "train.ckpt.snapshot", "train.report")
    # the commit is the background thread's: never inside the step's report
    assert not _inside(train_capture, "train.ckpt.commit", "train.report")
    batch = [st for n, _, _, st in train_capture if n == "data.next_batch" and st]
    assert batch[0]["rows"] == 4 and batch[0]["bytes"] == 4 * 16 * 4


# ------------------------------------- a debug engine, three requests
@pytest.fixture(scope="module")
def serve_capture(tmp_path_factory):
    from ray_tpu.llm.serving import LLMDeployment

    server = LLMDeployment("debug-128", max_slots=4, max_len=128, request_timeout_s=60)
    out = {}
    try:
        server.generate("warm up the programs", max_new_tokens=4)
        before = dict(server.engine.metrics)

        def work():
            def request(i):
                # a root trace of its own: the engine then records its spans
                with tracing.span("clockcheck"):
                    out[i] = server.generate(f"request {i} " + "ab" * (3 + 9 * i),
                                             max_new_tokens=6)

            threads = [threading.Thread(target=request, args=(i,)) for i in range(3)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            out["wall_ms"] = (time.monotonic() - t0) * 1e3
            time.sleep(0.12)  # the loop, idle again: two whole serving.idle events

        with _recorded_spans() as spans:
            path = _captured(work, tmp_path_factory.mktemp("serve"))
        after = dict(server.engine.metrics)
    finally:
        server.close()
    delta = {k: after[k] - before[k] for k in (
        "steps", "step_host_ms_sum", "step_sync_ms_sum", "queue_wait_ms_sum",
        "queue_wait_count")}
    return {"events": _events(path), "delta": delta, "out": out, "path": path,
            "spans": spans}


@pytest.mark.parametrize("name", [
    "engine.step", "engine.admit", "engine.dispatch", "engine.sync",
    "engine.emit", "serving.push", "serving.idle"])
def test_engine_span_is_on_the_host_plane(serve_capture, name):
    assert name in {n for n, *_ in serve_capture["events"]}


@pytest.mark.parametrize("inner", ["engine.admit", "engine.dispatch",
                                   "engine.sync", "engine.emit"])
def test_engine_spans_nest_inside_the_step(serve_capture, inner):
    assert _inside(serve_capture["events"], inner, "engine.step")
    assert not _inside(serve_capture["events"], "serving.push", "engine.step")


def test_engine_dispatch_says_what_it_enqueued(serve_capture):
    kinds = {st.get("kind") for n, _, _, st in serve_capture["events"]
             if n == "engine.dispatch"}
    assert kinds and kinds <= {"mixed", "prefill", "decode", "flush", "verify"}
    steps = [st for n, _, _, st in serve_capture["events"] if n == "engine.step"]
    assert all({"waiting", "prefilling", "active"} <= set(st) for st in steps)


def test_engine_counters_add_up(serve_capture):
    d = serve_capture["delta"]
    assert d["queue_wait_count"] == 3          # one per admitted request
    assert d["queue_wait_ms_sum"] >= 0.0
    assert d["steps"] > 0 and d["step_sync_ms_sum"] > 0.0
    assert d["step_host_ms_sum"] > 0.0
    # host + sync is the steps' own wall time, which the requests' outlasts
    assert d["step_host_ms_sum"] + d["step_sync_ms_sum"] <= serve_capture["out"]["wall_ms"]
    steps = [e - s for n, s, e, _ in serve_capture["events"] if n == "engine.step"]
    assert len(steps) == d["steps"]
    assert sum(steps) * 1e3 == pytest.approx(
        d["step_host_ms_sum"] + d["step_sync_ms_sum"], rel=0.05, abs=2.0)


def test_prefill_span_carries_queue_wait_and_chunks():
    from ray_tpu.llm.engine import InferenceEngine, Request
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["debug-128"]
    eng = InferenceEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                          max_slots=2, max_len=128, page_size=16,
                          prefill_chunk_size=16)
    with _recorded_spans() as spans:
        with tracing.span("prefill-span-test"):
            r = Request("r0", list(range(1, 41)), max_new_tokens=2)
            eng.add_request(r)
        while not r.done:
            eng.step()
    span = [s for s in spans if s["name"] == "llm.prefill"][0]
    assert span["attrs"]["prefill_chunks"] == r.prefill_chunks >= 3  # 40 tokens by 16
    assert span["attrs"]["queue_wait_ms"] == pytest.approx(
        (r.admitted_at - r.arrived_at) * 1e3, abs=1e-2)
    assert eng.metrics["queue_wait_count"] == 1


# ------------------------------------------- the window and the two clocks
def test_capture_window_carries_both_clocks_and_maps_a_wall_clock_span(serve_capture):
    window = [ev for ev in serve_capture["events"] if ev[0] == profile.WINDOW]
    assert len(window) == 1
    _, lo, hi, stats = window[0]
    assert abs(stats["wall_s"] - time.time()) < 3600 and stats["mono_s"] > 0
    # the engine's llm.prefill spans are wall-clock (time.time()) and were
    # recorded inside the capture: mapped through wall_s they lie inside it
    dapper = [s for s in serve_capture["spans"] if s["name"] == "llm.prefill"]
    assert len(dapper) == 3 and all(s["start"] >= stats["wall_s"] for s in dapper)
    summary = profile.summarize(serve_capture["path"], spans=dapper)
    row = {s["name"]: s for s in summary["spans"]}["llm.prefill"]
    assert row["count"] == len(dapper)
    assert row["total_ms"] == pytest.approx(
        sum(s["end"] - s["start"] for s in dapper) * 1e3, rel=1e-3)
    assert summary["window"]["seconds"] == pytest.approx(hi - lo)
    assert {"engine.step", "engine.sync"} <= set(
        s["name"] for s in summary["spans"])


# ------------------------------------------------------ summarize, synthetic
FLASH = ('%flash_fwd.18 = (bf16[2,16,4096,128]{3,2,1,0}) custom-call(bf16[2,16,4096,128] %q), '
         'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')


@pytest.fixture()
def synthetic(tmp_path):
    """Device busy 1-2, 4-5 and 8-9 ms of a 0-10 ms window. The gap 2-4 ms
    lies in engine.step AND in its engine.sync; 5-8 ms in engine.step alone;
    9-10 ms under no span, only a Python frame; 0-1 ms under nothing."""
    return xplane_writer.write(str(tmp_path / "s.xplane.pb"), {
        "/device:TPU:0": {"XLA Ops": [("%fusion.3 = bf16[8] fusion(%p)", 1.0, 1.0),
                                      (FLASH, 4.0, 1.0),
                                      ("%fusion.4 = bf16[8] fusion(%p)", 8.0, 1.0)],
                          "XLA Modules": [("jit_step(1)", 1.0, 8.0)]},
        "/host:CPU": {
            "main/1": [(profile.WINDOW, 0.0, 10.0, {"wall_s": 1000.0, "mono_s": 5.0})],
            "engine/2": [("engine.step", 1.5, 7.0, {"active": 2.0, tracing.MARK: 1.0}),
                         ("engine.sync", 1.8, 2.4, {tracing.MARK: 1.0}),
                         # XLA's own host events are named alike: no mark, no span
                         ("dot_general.56", 1.9, 0.2)],
            "python": [("$serving.py:262 _engine_loop", 0.6, 9.4)]}})


def test_summarize_gives_a_gap_to_the_innermost_program_span(synthetic):
    s = profile.summarize(synthetic)
    assert s["window"]["seconds"] == pytest.approx(0.010) and s["window"]["wall_s"] == 1000.0
    assert s["devices"] == [{"name": "/device:TPU:0", "busy_s": pytest.approx(0.003),
                             "idle_s": pytest.approx(0.007)}]
    by_span = dict(s["idle_by_span"])
    assert by_span["engine.sync"] == pytest.approx(0.002)
    assert by_span["engine.step"] == pytest.approx(0.003)
    assert s["gaps_over_1ms"] == 2


def test_summarize_names_a_python_frame_only_where_no_span_covers(synthetic):
    s = profile.summarize(synthetic)
    uncovered = dict(s["idle_uncovered"])
    assert uncovered["$serving.py:262 _engine_loop"] == pytest.approx(0.001)  # 9-10 ms
    assert uncovered["no host event"] == pytest.approx(0.001)                 # 0-1 ms
    assert not set(uncovered) & {"engine.step", "engine.sync"}
    assert sum(dict(s["idle_by_span"]).values()) + sum(uncovered.values()) \
        == pytest.approx(s["devices"][0]["idle_s"])


def test_summarize_tables_ops_kernels_and_wall_clock_spans(synthetic):
    spans = [{"name": "llm.prefill", "start": 1000.002, "end": 1000.006, "attrs": {}},
             {"name": "llm.decode", "start": 990.0, "end": 999.0, "attrs": {}}]  # outside
    s = profile.summarize(synthetic, spans=spans)
    assert s["kernels"] == [["flash_fwd", pytest.approx(0.001), 1]]
    assert s["ops"][0] == ["%fusion", pytest.approx(0.002), 2]
    rows = {r["name"]: r for r in s["spans"]}
    assert rows["llm.prefill"]["total_ms"] == pytest.approx(4.0) and "llm.decode" not in rows
    assert rows["engine.step"]["count"] == 1 and "dot_general.56" not in rows
    assert "engine.sync" in profile.render(s)


def test_a_capture_without_its_window_event_is_refused(tmp_path):
    path = xplane_writer.write(str(tmp_path / "n.xplane.pb"), {
        "/host:CPU": {"main/1": [("engine.step", 0.0, 1.0, {tracing.MARK: 1.0})]}})
    with pytest.raises(ValueError, match="capture_window"):
        profile.summarize(path)


# ------------------------------------------------------------ kernel costs
def _qkv(b, hq, hkv, s, d):
    q = jnp.zeros((b, hq, s, d), jnp.bfloat16)
    kv = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    return q, kv, kv


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_costs_equal_a_hand_count(causal):
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.trace_log import kernel_costs

    b, hq, hkv, s, d = 1, 4, 2, 64, 128
    q, k, v = _qkv(b, hq, hkv, s, d)
    jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=causal).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    costs = kernel_costs()
    matmul = 2 * b * hq * s * s * d / (2 if causal else 1)  # one [S,S] x D product
    q_b, kv_b, stats = b * hq * s * d * 2, 2 * b * hkv * s * d * 2, b * hq * s * 4
    rows = 128 * stats  # the forward writes lse over 128 lanes; the backward reads it compact
    assert costs["flash_fwd"]["flops"] == 2 * matmul
    assert costs["flash_fwd"]["bytes"] == 2 * q_b + kv_b + rows       # q k v; o lse
    assert costs["flash_bwd_dq"]["flops"] == 3 * matmul
    assert costs["flash_bwd_dq"]["bytes"] == 3 * q_b + kv_b + 2 * stats  # q k v dO lse delta; dQ
    assert costs["flash_bwd_dkdv"]["flops"] == 4 * matmul
    assert costs["flash_bwd_dkdv"]["bytes"] == 2 * q_b + kv_b + 2 * stats + 2 * q_b  # ...; dK dV at Hq
    assert all(c["traced"] >= 1 for c in costs.values())


def test_paged_decode_cost_and_the_device_report():
    from ray_tpu.ops.paged_attention import paged_decode_attention
    from ray_tpu.tpu import device_report

    n, kh, g, d, page, pages = 2, 2, 4, 128, 16, 8
    q = jnp.zeros((n, kh, g, d), jnp.bfloat16)
    pool = jnp.zeros((n + n * pages, kh, page, d), jnp.bfloat16)
    tables = jnp.tile(jnp.arange(pages, dtype=jnp.int32)[None] + n, (n, 1))
    paged_decode_attention(q, pool, pool, tables, jnp.asarray([20, 40], jnp.int32),
                           page_size=page, live_pages=4,
                           k_cur=jnp.zeros((n, kh, d), jnp.bfloat16),
                           v_cur=jnp.zeros((n, kh, d), jnp.bfloat16))
    cost = device_report()["kernel_costs"]["paged_decode"]
    ctx = 4 * page + 16  # the live_pages bound plus the staging tile
    assert cost["flops"] == 4 * n * kh * g * d * ctx
    assert cost["bytes"] == 2 * n * kh * g * d * 2 + 2 * n * kh * ctx * d * 2
    assert "kernel_traces" in device_report()


def test_summarize_tables_the_step_by_scope(tmp_path):
    """Self time by ``rt_scope`` path on device 0, rolled up to a path's
    first and last component; an op without one is under ""."""
    op = lambda name, scope: (  # noqa: E731
        f"%{name} = bf16[8] fusion(bf16[8] %p), kind=kLoop, calls=%fc"
        + (f', frontend_attributes={{rt_scope="{scope}"}}' if scope else ""))
    kernel = ('%gdn_fwd.3 = bf16[8] custom-call(bf16[8] %p), custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata={},rt_scope="stack/attn/gdn_scan"}')
    path = xplane_writer.write(str(tmp_path / "s.xplane.pb"), {
        "/device:TPU:0": {"XLA Ops": [
            ("%while.1 = (s32[]) while((s32[]) %t), body=%b, "
             'frontend_attributes={rt_scope="stack"}', 0.0, 6.0),     # self: 6 - 4.5
            (op("fusion.1", "stack/attn"), 0.5, 1.0), (kernel, 1.5, 2.0),
            (op("fusion.2", "stack/mlp/moe_experts"), 3.5, 1.0),
            (op("fusion.3", "stack/attn_gate"), 4.5, 0.5),            # no ``attn``
            (op("fusion.4", "lm_head_loss"), 6.0, 2.0), (op("fusion.5", ""), 8.0, 2.0)]},
        "/host:CPU": {"main/1": [(profile.WINDOW, 0.0, 10.0, {"wall_s": 1.0, "mono_s": 1.0})]}})
    s = profile.summarize(path)
    ms = lambda key: {r[0]: round(r[1] * 1e3, 6) for r in s["scopes"][key]}  # noqa: E731
    assert ms("path") == {"stack": 1.5, "stack/attn": 1.0, "stack/attn/gdn_scan": 2.0,
                          "stack/mlp/moe_experts": 1.0, "stack/attn_gate": 0.5,
                          "lm_head_loss": 2.0, "": 2.0}
    assert ms("first") == {"stack": 6.0, "lm_head_loss": 2.0, "": 2.0}
    assert ms("last") == {"stack": 1.5, "attn": 1.0, "gdn_scan": 2.0, "moe_experts": 1.0,
                          "attn_gate": 0.5, "lm_head_loss": 2.0, "": 2.0}
    for key in ("path", "first", "last"):  # [name, self s, count, % of busy], largest first
        rows = s["scopes"][key]
        assert sum(r[3] for r in rows) == pytest.approx(100.0)
        assert [r[1] for r in rows] == sorted((r[1] for r in rows), reverse=True)
    assert [r for r in s["scopes"]["path"] if r[0] == "stack/attn/gdn_scan"][0][2] == 1
    text = profile.render(s)
    assert "device time by scope path" in text and "stack/attn/gdn_scan" in text
    assert "(no scope)" in text


def test_a_capture_whose_names_carry_no_scope_renders_no_scope_table(synthetic):
    s = profile.summarize(synthetic)
    assert [r[0] for r in s["scopes"]["path"]] == [""]
    assert s["scopes"]["path"][0][3] == pytest.approx(100.0)
    assert "by scope" not in profile.render(s)
    # nor a pass (``tests/benchmark_suite/test_bm_passes.py`` reads one that does)
    assert [r[:1] for r in s["passes"]["pass"]] == [[""]] and "by pass" not in profile.render(s)


# ------------------------------------- one capture, on the worker asked for
@pytest.fixture(scope="module")
def stepper(train_capture):
    """A named actor that has imported jax and steps under ``engine.step``
    for as long as asked."""
    import ray_tpu

    @ray_tpu.remote
    class Stepper:
        def run(self, seconds: float) -> int:
            import jax.numpy as jnp

            from ray_tpu.observability.tracing import annotate

            t0, n = time.monotonic(), 0
            while time.monotonic() - t0 < seconds:
                with annotate("engine.step", active=1):
                    float(jnp.ones(8).sum())
                    time.sleep(0.005)
                n += 1
            return n

    actor = Stepper.options(name="stepper-for-profile").remote()
    assert ray_tpu.get(actor.run.remote(0.05), timeout=60) > 0  # jax is imported there now
    yield actor
    ray_tpu.kill(actor)


def test_capture_profile_by_actor_name_returns_that_workers_summary(stepper):
    import ray_tpu
    from ray_tpu.util import state

    running = stepper.run.remote(4.0)
    reply = state.capture_profile(actor="stepper-for-profile", duration=0.5, summary=True)
    assert "error" not in reply and "summary_error" not in reply, reply
    row = [a for a in state.list_actors() if a.get("name") == "stepper-for-profile"][0]
    assert reply["worker_id"] == row["worker_id"]
    summary = reply["summary"]
    assert summary["window"]["seconds"] == pytest.approx(0.5, abs=0.2)
    assert summary["window"]["wall_s"] == pytest.approx(time.time(), abs=120)
    steps = {s["name"]: s for s in summary["spans"]}["engine.step"]
    assert steps["count"] > 5 and steps["total_ms"] <= summary["window"]["seconds"] * 1e3
    assert all(d["busy_s"] <= summary["window"]["seconds"] for d in summary["devices"])
    assert state.capture_profile(actor="no-such-actor", duration=0.1)["error"]
    ray_tpu.get(running, timeout=60)


@pytest.mark.parametrize("reader_limit,arrives", [(None, True), (0.05, False)])
def test_a_summary_that_outlasts_the_capture(stepper, monkeypatch, reader_limit, arrives):
    """Reading is a step of its own: a read several times the capture's
    length still arrives, and one past its limit costs the summary alone
    (the capture is back, registered, and the worker free for the next)."""
    from ray_tpu.util import state

    real, took = profile.summarize_apart, []

    def slow(path, timeout):
        time.sleep(0.0 if reader_limit else 1.5)  # five times the capture
        t0 = time.monotonic()
        try:
            return real(path, reader_limit or timeout)
        finally:
            took.append(time.monotonic() - t0)

    monkeypatch.setattr(profile, "summarize_apart", slow)  # the raylet is in this process
    reply = state.capture_profile(actor="stepper-for-profile", duration=0.3, summary=True)
    assert "error" not in reply and took, reply
    assert reply["path"] in [p["path"] for p in state.list_profiles()]
    if arrives:
        assert reply["summary"]["window"]["seconds"] == pytest.approx(0.3, abs=0.2)
    else:
        assert "summary" not in reply and "TimeoutExpired" in reply["summary_error"]
        again = state.capture_profile(actor="stepper-for-profile", duration=0.1)
        assert again.get("path"), again  # not "already in progress"


def test_the_limit_grows_with_the_capture():
    # a saturated replica's capture took 15.1 s per captured second to stop and
    # export (30.2 / 60.7 / 120.6 s for 2 / 4 / 8 s; PERF.md, PR 24)
    from ray_tpu.core.config import get_config

    for duration in (2.0, 8.0, get_config().profile_max_duration_s):
        assert profile.limit_s(duration) >= 2 * 15.1 * duration


def test_summarize_profile_refuses_a_path_that_is_no_capture_of_the_node(stepper, tmp_path):
    from ray_tpu.core.rpc import RpcClient
    from ray_tpu.core.worker import global_worker
    from ray_tpu.util import state

    node = [n for n in state.list_nodes() if n["state"] == "ALIVE"][0]

    async def call():
        client = RpcClient(node["address"])
        try:
            return await client.call("SummarizeProfile",
                                     {"path": str(tmp_path), "timeout": 5.0}, timeout=30.0)
        finally:
            await client.close()

    assert "not a capture of this node" in global_worker().io.run_sync(call())["error"]
