"""Overload protection (round 12): end-to-end request deadlines, bounded
queues with cost-aware load shedding, engine admission watermark, and
replica circuit breaking.

The regime under test is the millisecond one where offered load exceeds
capacity: the system must degrade gracefully — bounded TTFT for admitted
work, fast honest 503s (with Retry-After) for the rest, deadline
expiries that never burn engine capacity — instead of the classic
congestion collapse where every request's TTFT blows up together. The
chaos storm at the bottom must drain back to a RecoveryVerifier-green
state with page-pool refcounts at baseline.
"""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import get_config
from ray_tpu.llm.engine import InferenceEngine, QueueFullError, Request
from ray_tpu.models.llama import PRESETS, forward, init_params
from ray_tpu.serve.router import DeadlineExceeded, RequestShed


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def naive_greedy(params, cfg, prompt, n):
    toks, out = list(prompt), []
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks]), cfg)[0, -1]
        t = int(jnp.argmax(logits))
        out.append(t)
        toks.append(t)
    return out


def _bare_router(replicas: dict[str, int]):
    """Router skeleton for overload-policy unit tests: real assign/
    release/shed/circuit logic, no controller or long-poll behind it."""
    from collections import OrderedDict

    from ray_tpu.serve.router import Router

    r = Router.__new__(Router)
    r._key = "replicas::app::dep"
    r._lock = threading.Lock()
    r._cond = threading.Condition(r._lock)
    r._replicas = {rid: {"actor": f"actor-{rid}", "max_ongoing": cap}
                   for rid, cap in replicas.items()}
    r._inflight = {rid: 0 for rid in replicas}
    r._model_affinity = {}
    r._group_affinity = OrderedDict()
    r.affinity_stats = {"hits": 0, "misses": 0, "spills": 0,
                        "new_groups": 0}
    r.spill_migrations = 0
    r._init_overload_state()
    return r


@pytest.fixture()
def overload_cfg():
    """Config sandbox: tests mutate the overload knobs freely."""
    cfg = get_config()
    saved = (cfg.serve_max_queued_requests, cfg.serve_shed_policy,
             cfg.serve_circuit_breaker_failures,
             cfg.serve_circuit_breaker_cooldown_s)
    yield cfg
    (cfg.serve_max_queued_requests, cfg.serve_shed_policy,
     cfg.serve_circuit_breaker_failures,
     cfg.serve_circuit_breaker_cooldown_s) = saved


# --------------------------------------------------------------- router units
def test_router_queue_bound_sheds_fast(overload_cfg):
    """ISSUE 12: over the router queue bound, the incoming request is
    shed with a FAST RequestShed (503 semantics) carrying a Retry-After,
    instead of joining an unbounded wait."""
    overload_cfg.serve_max_queued_requests = 2
    router = _bare_router({"r1": 1})
    router.assign_replica()  # saturate the single slot
    waiters, started = [], []

    def wait_one():
        started.append(1)
        try:
            waiters.append(router.assign_replica(timeout=10.0))
        except Exception as e:
            waiters.append(e)

    threads = [threading.Thread(target=wait_one, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5
    while len(started) < 2 or router.overload_snapshot()["queued"] < 2:
        assert time.monotonic() < deadline, "waiters never queued"
        time.sleep(0.01)
    t0 = time.monotonic()
    with pytest.raises(RequestShed) as ei:
        router.assign_replica(timeout=10.0)
    fast_fail_ms = 1000 * (time.monotonic() - t0)
    assert fast_fail_ms < 100, f"shed took {fast_fail_ms:.0f}ms"
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after >= 1
    assert router.overload_snapshot()["shed"] == {"queue_full": 1}
    # free the slot: both queued waiters eventually get served (each
    # release lets exactly one through the 1-slot replica)
    router.release("r1")
    deadline = time.monotonic() + 10
    while sum(1 for w in waiters if isinstance(w, tuple)) < 1:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    router.release("r1")
    for t in threads:
        t.join(timeout=10)
    assert sum(1 for w in waiters if isinstance(w, tuple)) == 2


def test_router_cost_aware_shed_prefers_cold(overload_cfg):
    """Cost-aware shedding: a request whose prefix group's KV is
    resident (cheap — small cold suffix) preempts a COLD waiter's queue
    slot; the cold waiter gets the fast 503, the cheap one is served."""
    overload_cfg.serve_max_queued_requests = 1
    overload_cfg.serve_shed_policy = "cost"
    router = _bare_router({"r1": 1})
    first, _ = router.assign_replica(prefix_group="sess:hot")  # maps group
    outcome = {}

    def cold_waiter():
        try:
            outcome["cold"] = router.assign_replica(timeout=10.0)
        except Exception as e:
            outcome["cold"] = e

    t_cold = threading.Thread(target=cold_waiter, daemon=True)
    t_cold.start()
    deadline = time.monotonic() + 5
    while router.overload_snapshot()["queued"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.01)

    def cheap_waiter():
        try:
            outcome["cheap"] = router.assign_replica(
                prefix_group="sess:hot", timeout=10.0)
        except Exception as e:
            outcome["cheap"] = e

    t_cheap = threading.Thread(target=cheap_waiter, daemon=True)
    t_cheap.start()
    t_cold.join(timeout=10)
    assert isinstance(outcome.get("cold"), RequestShed)
    assert outcome["cold"].reason == "preempted"
    router.release(first)
    t_cheap.join(timeout=10)
    assert isinstance(outcome.get("cheap"), tuple)
    shed = router.overload_snapshot()["shed"]
    assert shed.get("preempted") == 1
    # fifo policy: the incoming request sheds even when cheap
    overload_cfg.serve_shed_policy = "fifo"
    router2 = _bare_router({"r1": 1})
    router2.assign_replica(prefix_group="sess:h2")
    t = threading.Thread(
        target=lambda: router2.assign_replica(timeout=10.0), daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while router2.overload_snapshot()["queued"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    with pytest.raises(RequestShed):
        router2.assign_replica(prefix_group="sess:h2", timeout=10.0)
    router2.release("r1")
    t.join(timeout=10)


def test_router_deadline_expires_in_queue(overload_cfg):
    """A request whose deadline expires while WAITING in the router
    raises DeadlineExceeded (504 semantics) promptly and is counted."""
    router = _bare_router({"r1": 1})
    router.assign_replica()
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        router.assign_replica(timeout=30.0, deadline=time.time() + 0.3)
    assert time.monotonic() - t0 < 2.0
    assert router.overload_snapshot()["deadline_expired_queued"] == 1
    # an ALREADY-expired deadline fails without blocking at all
    with pytest.raises(DeadlineExceeded):
        router.assign_replica(timeout=30.0, deadline=time.time() - 1.0)


def test_circuit_breaker_open_half_open_close(overload_cfg):
    """ISSUE 12 circuit breaker: N consecutive handle timeouts open the
    replica's circuit (traffic reroutes), the cooldown admits ONE
    half-open probe, probe success closes, probe failure re-opens."""
    overload_cfg.serve_circuit_breaker_failures = 3
    overload_cfg.serve_circuit_breaker_cooldown_s = 0.2
    router = _bare_router({"bad": 4, "good": 4})
    # two timeouts: still closed (streak below N)
    router.note_request_failure("bad", timeout=True)
    router.note_request_failure("bad", timeout=True)
    assert router.circuit_state("bad") == "closed"
    # a success resets the streak
    router.note_request_success("bad")
    for _ in range(2):
        router.note_request_failure("bad", timeout=True)
    assert router.circuit_state("bad") == "closed"
    router.note_request_failure("bad", timeout=True)
    assert router.circuit_state("bad") == "open"
    assert router.overload_snapshot()["circuit_opens"] == 1
    # open: every assignment lands on the healthy replica
    for _ in range(6):
        rid, _a = router.assign_replica(timeout=1.0)
        assert rid == "good"
        router.release(rid)
    # non-timeout failures never trip the breaker
    router.note_request_failure("good", timeout=False)
    assert router.circuit_state("good") == "closed"
    # cooldown elapses -> half-open, ONE probe admitted at a time
    time.sleep(0.25)
    picks = set()
    a1 = router.assign_replica(timeout=1.0)  # may pick bad (the probe)
    picks.add(a1[0])
    if a1[0] == "bad":
        assert router.circuit_state("bad") == "half_open"
        # probe in flight: a second assignment must avoid the replica
        rid2, _ = router.assign_replica(timeout=1.0)
        assert rid2 == "good"
        router.release(rid2)
        # probe FAILS -> re-open immediately
        router.note_request_failure("bad", timeout=True)
        assert router.circuit_state("bad") == "open"
        router.release("bad")
        time.sleep(0.25)
    else:
        router.release(a1[0])
    # drive until the probe lands on bad, then let it SUCCEED
    deadline = time.monotonic() + 5
    while True:
        assert time.monotonic() < deadline
        rid, _a = router.assign_replica(timeout=1.0)
        if rid == "bad":
            router.note_request_success("bad")
            router.release("bad")
            break
        router.release(rid)
        time.sleep(0.05)
    assert router.circuit_state("bad") == "closed"
    snap = router.overload_snapshot()
    assert "bad" not in snap["circuit"]  # closed entries not reported


def test_all_replicas_circuit_open_sheds(overload_cfg):
    """When every replica's circuit is open (and still cooling), the
    request is shed immediately with reason circuit_open — queueing for
    a fleet of tripped replicas is the collapse we refuse."""
    overload_cfg.serve_circuit_breaker_failures = 1
    overload_cfg.serve_circuit_breaker_cooldown_s = 30.0
    router = _bare_router({"r1": 4, "r2": 4})
    router.note_request_failure("r1", timeout=True)
    router.note_request_failure("r2", timeout=True)
    t0 = time.monotonic()
    with pytest.raises(RequestShed) as ei:
        router.assign_replica(timeout=10.0)
    assert time.monotonic() - t0 < 1.0
    assert ei.value.reason == "circuit_open"


# --------------------------------------------------------------- engine units
def test_deadline_expiry_in_queue_never_reaches_engine(small_model):
    """ISSUE 12 deadline semantics: a request whose deadline expired
    while WAITING is settled by the sweep without a slot, a page, or a
    prefill chunk — it never touches the engine."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    free_before = len(eng.allocator.free)
    chunks_before = eng.metrics["prefill_chunks"]
    r = Request("dead", list(range(1, 20)), max_new_tokens=4,
                deadline=time.time() - 0.1)
    eng.add_request(r)
    events = eng.step()
    assert r.done and r.finish_reason == "deadline"
    assert [e for e in events if e["request_id"] == "dead"] == [
        {"request_id": "dead", "token": -1, "done": True,
         "finish_reason": "deadline"}]
    assert eng.metrics["deadline_expired_queued"] == 1
    assert eng.metrics["deadline_expired_running"] == 0
    assert eng.metrics["prefill_chunks"] == chunks_before
    assert len(eng.allocator.free) == free_before
    assert eng.pool_stats()["pinned"] == 0
    # a live request beside it is unaffected
    ok = Request("ok", list(range(1, 20)), max_new_tokens=4)
    eng.add_request(ok)
    while not ok.done:
        eng.step()
    assert ok.generated == naive_greedy(params, cfg, list(range(1, 20)), 4)


def test_deadline_mid_decode_aborts_and_frees_pages_same_tick(small_model):
    """A deadline that expires MID-DECODE aborts the slot the same tick:
    pages and pins return to the pool (accounting back to baseline), the
    stream gets a terminal 'deadline' event, and the freed capacity
    serves the next request."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          decode_steps_per_dispatch=1)
    baseline = eng.pool_stats()
    prompt = list(range(1, 20))
    r = Request("mid", list(prompt), max_new_tokens=40)
    eng.add_request(r)
    # drive through prefill + a few decode ticks
    while r.slot < 0 or len(r.generated) < 2:
        eng.step()
    assert not r.done
    assert eng.pool_stats()["pinned"] > 0
    r.deadline = time.time() - 0.01
    events = eng.step()
    assert r.done and r.finish_reason == "deadline"
    assert any(e["request_id"] == "mid" and e["finish_reason"] == "deadline"
               for e in events)
    assert eng.metrics["deadline_expired_running"] == 1
    stats = eng.pool_stats()
    # Pages freed THIS tick: nothing pinned, no active slot; computed
    # pages enter the prefix cache (free + cached conserves the pool).
    assert stats["pinned"] == 0 and stats["active_slots"] == 0
    assert stats["free"] + stats["cached"] == \
        baseline["free"] + baseline["cached"]
    # byte parity for a follow-up that reuses the cached prefix
    b = Request("after", list(prompt), max_new_tokens=4)
    eng.add_request(b)
    while not b.done:
        eng.step()
    assert b.generated == naive_greedy(params, cfg, prompt, 4)


def test_deadline_mid_prefill_and_pending_first(small_model):
    """Expiry while chunk-prefilling (or awaiting the batched first
    sample) is a 'running' abort: retired, pages freed, handle dropped."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          prefill_chunk_size=8)
    r = Request("pf", list(range(1, 30)), max_new_tokens=4)
    eng.add_request(r)
    eng.step()  # admit + first prefill chunk only (chunked)
    assert r.slot >= 0 and not r.done
    r.deadline = time.time() - 0.01
    eng.step()
    assert r.done and r.finish_reason == "deadline"
    assert eng.metrics["deadline_expired_running"] == 1
    assert eng.pool_stats()["pinned"] == 0
    assert eng.pool_stats()["active_slots"] == 0


def test_engine_queue_bound_sheds(small_model):
    """Per-replica bounded admission queue: over max_queued_requests,
    add_request sheds with QueueFullError (503 + Retry-After shape)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          max_queued_requests=2)
    for i in range(2):
        eng.add_request(Request(f"q{i}", [1, 2, 3], max_new_tokens=2))
    with pytest.raises(QueueFullError) as ei:
        eng.add_request(Request("q2", [1, 2, 3], max_new_tokens=2))
    assert ei.value.http_status.startswith("503")
    assert ei.value.retry_after >= 1
    assert eng.metrics["queue_rejects"] == 1
    # the bounded queue drains normally
    while eng.has_work:
        eng.step()
    assert eng.pool_stats()["pinned"] == 0


def test_admission_watermark_rejects_and_recovers(small_model):
    """Admission refuses (and counts) while free pages sit below the
    reserve — the request stays QUEUED, is never bounced to the client,
    and admits as soon as capacity frees."""
    cfg, params = small_model
    # Pool sized so one 24-token+growth request fits but two do not.
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          num_pages=8, enable_prefix_cache=False)
    a = Request("a", list(range(1, 25)), max_new_tokens=24)
    b = Request("b", list(range(30, 54)), max_new_tokens=24)
    eng.add_request(a)
    eng.add_request(b)
    eng.step()
    assert a.slot >= 0
    assert eng.metrics["admission_rejects"] >= 1
    with eng._lock:
        assert len(eng._waiting) == 1  # b queued, not failed
    assert not b.done
    while not a.done:
        eng.step()
    while not b.done:
        eng.step()
    assert b.finish_reason in ("length", "max_len", "stop")
    assert eng.pool_stats()["pinned"] == 0


def test_admission_watermark_reserve_pages(small_model):
    """A nonzero admission watermark holds back free-page headroom:
    admission that would dip into the reserve defers instead."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          num_pages=8, enable_prefix_cache=False,
                          admission_watermark_pages=6)
    r = Request("w", list(range(1, 25)), max_new_tokens=24)  # needs 6 pages
    eng.add_request(r)
    eng.step()
    assert r.slot < 0 and not r.done  # 8 free - 6 needed < 6 reserve
    assert eng.metrics["admission_rejects"] >= 1
    eng.admission_watermark_pages = 0
    while not r.done:
        eng.step()
    assert eng.pool_stats()["pinned"] == 0


# ------------------------------------------------------------------- e2e http
@pytest.fixture()
def serve_instance(ray_cluster):
    yield
    serve.shutdown()


def _post(addr, path, body: dict, headers: dict | None = None,
          timeout: float = 60.0):
    """Returns (status_code_or_error_name, raw_body, headers)."""
    req = urllib.request.Request(
        addr + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw = r.read()
            return r.status, raw, dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)
    except Exception as e:
        return type(e).__name__, b"", {}


def test_deadline_rides_header_across_proxy_hop(serve_instance):
    """The x-raytpu-deadline-ms header stamped at ingress is visible to
    the user callable via serve.get_request_deadline(), absolute-clock."""

    @serve.deployment(num_replicas=1)
    class DeadlineEcho:
        def __call__(self, request):
            d = serve.get_request_deadline()
            return {"deadline": d, "now": time.time()}

    serve.run(DeadlineEcho.bind(), name="dl", route_prefix="/dl")
    addr = serve.http_address()
    t_sent = time.time()
    status, raw, _h = _post(addr, "/dl", {},
                            headers={"x-raytpu-deadline-ms": "5000"})
    t_answered = time.time()
    assert status == 200
    out = json.loads(raw)
    assert out["deadline"] is not None
    # Stamped at ingress on the absolute clock: between this client's send
    # and its answer, plus the budget, however long the hop to the replica
    # took (replaces `1.0 < deadline - now <= 5.5`, which a slow hop fails).
    assert t_sent + 5.0 <= out["deadline"] <= t_answered + 5.0, out
    assert t_sent <= out["now"] <= t_answered, out
    # no header, no default -> no deadline
    status, raw, _h = _post(addr, "/dl", {})
    assert json.loads(raw)["deadline"] is None
    # a timeout_s body field works as the budget too
    t_sent = time.time()
    status, raw, _h = _post(addr, "/dl", {"timeout_s": 3})
    t_answered = time.time()
    out = json.loads(raw)
    assert out["deadline"] is not None and \
        t_sent + 3.0 <= out["deadline"] <= t_answered + 3.0, out
    serve.delete("dl")


def test_proxy_replica_death_returns_503_retry_after(serve_instance):
    """Satellite (b): when the routed replica is dead (retry path
    exhausted), the proxy answers 503 + Retry-After, not a bare 500."""

    @serve.deployment(num_replicas=1)
    class Pid:
        def __call__(self, request):
            import os

            return {"pid": os.getpid()}

    serve.run(Pid.bind(), name="die", route_prefix="/die")
    addr = serve.http_address()
    status, raw, _h = _post(addr, "/die", {})
    assert status == 200
    pid = json.loads(raw)["pid"]
    import os
    import signal

    os.kill(pid, signal.SIGKILL)
    # Until the controller replaces the replica, requests that land on
    # the corpse must see an honest 503 with Retry-After (and once the
    # replacement is up, 200 again).
    saw_503 = False
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        status, raw, headers = _post(addr, "/die", {}, timeout=30)
        if status == 503:
            saw_503 = True
            assert headers.get("Retry-After"), headers
            break
        if status == 200 and json.loads(raw)["pid"] != pid:
            break  # replaced before we caught the window — rerun the kill
        time.sleep(0.05)
    if not saw_503:
        # raced the replacement: kill again and catch the window
        status, raw, _h = _post(addr, "/die", {})
        os.kill(json.loads(raw)["pid"], signal.SIGKILL)
        status, raw, headers = _post(addr, "/die", {}, timeout=30)
        if status == 503:
            saw_503 = True
            assert headers.get("Retry-After"), headers
    assert saw_503, "replica death never surfaced as 503 + Retry-After"
    serve.delete("die")


def test_llm_engine_queue_shed_e2e_503(serve_instance):
    """Through the real proxy: a replica whose bounded engine queue is
    full sheds with 503 + Retry-After while admitted requests complete;
    serve.status() surfaces the shed/queue counters."""
    from ray_tpu.llm import build_llm_app

    serve.run(build_llm_app("debug-128", num_replicas=1, max_slots=1,
                            max_len=128, page_size=16,
                            prefill_chunk_size=32,
                            max_queued_requests=1,
                            max_ongoing_requests=32),
              name="shed", route_prefix="/shed")
    addr = serve.http_address()
    # warm the compile caches so the storm is about queueing, not XLA
    _post(addr, "/shed/v1/completions", {"prompt": "warm" * 10,
                                         "max_tokens": 2}, timeout=60)
    results = []
    lock = threading.Lock()

    def fire(i):
        status, _raw, headers = _post(
            addr, "/shed/v1/completions",
            {"prompt": f"storm {i}: " + "abcd" * 12, "max_tokens": 24,
             "stream": True},
            timeout=60)
        with lock:
            results.append((status, headers.get("Retry-After")))

    threads = [threading.Thread(target=fire, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    statuses = [s for s, _ra in results]
    assert statuses.count(200) >= 1, results
    sheds = [(s, ra) for s, ra in results if s == 503]
    assert sheds, f"no 503 sheds under 8x concurrency on 1 slot: {results}"
    assert all(ra for _s, ra in sheds), "503 without Retry-After"
    # the engine-side counters reach serve.status() via the probe
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["shed"]["LLMDeployment"]
        if (st.get("overload") or {}).get("queue_rejects"):
            break
        time.sleep(0.5)
    assert (st.get("overload") or {}).get("queue_rejects", 0) >= 1, st
    serve.delete("shed")


def test_llm_deadline_e2e_504_and_mid_decode(serve_instance):
    """Deadline end to end through the proxy: a microscopic budget fails
    fast (504 from the router queue, or an SSE stream that ends with
    finish_reason 'deadline'), and the pool drains back to baseline."""
    from ray_tpu.llm import build_llm_app

    serve.run(build_llm_app("debug-128", num_replicas=1, max_slots=2,
                            max_len=128, page_size=16,
                            prefill_chunk_size=32,
                            max_ongoing_requests=16),
              name="dl-llm", route_prefix="/dlm")
    addr = serve.http_address()
    _post(addr, "/dlm/v1/completions", {"prompt": "warm" * 10,
                                        "max_tokens": 2}, timeout=60)
    # Tiny budget + long generation: the deadline expires mid-decode and
    # the stream ends with finish_reason "deadline" — or the request
    # fails fast before admission (504 from the router queue / 503 if
    # even the response head missed the budget).
    status, raw, _h = _post(
        addr, "/dlm/v1/completions",
        {"prompt": "deadline me " + "xyzw" * 10, "max_tokens": 64,
         "stream": True},
        headers={"x-raytpu-deadline-ms": "100"}, timeout=60)
    if status == 200:
        finishes = [json.loads(line[6:])["choices"][0].get("finish_reason")
                    for line in raw.decode().splitlines()
                    if line.startswith("data: ")
                    and line.strip() != "data: [DONE]"]
        assert finishes and finishes[-1] == "deadline", finishes
    else:
        assert status in (503, 504), (status, raw[:200])
    # engine settles: nothing pinned after the abort
    h = serve.get_app_handle("dl-llm")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = h.options(method_name="pool_stats").remote().result(
            timeout=30)
        if stats["pinned"] == 0 and stats["active_slots"] == 0:
            break
        time.sleep(0.2)
    assert stats["pinned"] == 0 and stats["active_slots"] == 0
    m = h.options(method_name="overload_stats").remote().result(timeout=30)
    assert m["deadline_expired_running"] + m["deadline_expired_queued"] >= 1
    serve.delete("dl-llm")


# ------------------------------------------------------------------ chaos
@pytest.mark.chaos
def test_overload_storm_chaos_recovers_green(ray_cluster):
    """ISSUE 12 acceptance: the overload chaos plan — a deterministic
    thundering-herd arrival schedule against an app with one DELAYED
    replica (the bundled overload-storm FaultPlan) — must leave the
    RecoveryVerifier green after the storm drains: no stuck requests,
    queues drained, page-pool refcounts at baseline after the mid-decode
    deadline aborts."""
    from ray_tpu import chaos as chaos_mod
    from ray_tpu.chaos.verifier import RecoveryVerifier
    from ray_tpu.llm import build_llm_app

    verifier = RecoveryVerifier(timeout_s=60)
    baseline = verifier.snapshot_baseline()
    serve.run(build_llm_app("debug-128", num_replicas=2, max_slots=2,
                            max_len=128, page_size=16,
                            prefill_chunk_size=32,
                            max_queued_requests=2,
                            max_ongoing_requests=16),
              name="overload", route_prefix="/ovl")
    addr = serve.http_address()

    def one(i, deadline_ms=None, max_tokens=24, timeout=60.0):
        headers = {"Content-Type": "application/json"}
        if deadline_ms:
            headers["x-raytpu-deadline-ms"] = str(deadline_ms)
        req = urllib.request.Request(
            addr + "/ovl/v1/completions",
            data=json.dumps({"prompt": f"storm {i}: " + "abcd" * 10,
                             "max_tokens": max_tokens,
                             "stream": True}).encode(),
            headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                body = r.read().decode()
                return 200, body
        except urllib.error.HTTPError as e:
            e.read()
            return e.code, ""
        except Exception as e:
            return type(e).__name__, ""

    # Warm both replicas' compile caches before the faults go in.
    warm = [threading.Thread(target=one, args=(f"w{i}",), daemon=True)
            for i in range(4)]
    for t in warm:
        t.start()
    for t in warm:
        t.join(timeout=60)

    # Install the plan in the driver AND inside every replica process —
    # the replica_delay fault fires where the handles execute.
    h = serve.get_app_handle("overload")
    router = h._get_router()
    deadline = time.monotonic() + 30
    while len(router._replicas) < 2:
        assert time.monotonic() < deadline
        time.sleep(0.1)

    def _install_in_replica(instance, seed):
        from ray_tpu import chaos as _c

        _c.install("overload-storm", seed, publish=False)
        return True

    def _uninstall_in_replica(instance):
        from ray_tpu import chaos as _c

        _c.uninstall()
        return True

    replicas = dict(router._replicas)
    for rid, r in replicas.items():
        assert ray_tpu.get(
            r["actor"].__ray_call__.remote(_install_in_replica, 0),
            timeout=60)
    chaos_mod.install("overload-storm", seed=0)
    statuses = []
    lock = threading.Lock()
    try:
        # Deterministic thundering herd: 3 bursts of 12 simultaneous
        # requests, each with a 1.5 s deadline, against 2 replicas x
        # (2 slots + 2 queued) with replica #2 stalling 400 ms per
        # handle — some complete, some shed 503, some expire 504 /
        # mid-decode.
        for burst in range(3):
            threads = []
            for i in range(12):
                t = threading.Thread(
                    target=lambda i=i: statuses.append(
                        one(f"b{burst}-{i}", deadline_ms=1500,
                            timeout=30.0)[0]),
                    daemon=True)
                threads.append(t)
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        chaos_mod.uninstall()
        for rid, r in replicas.items():
            try:
                ray_tpu.get(
                    r["actor"].__ray_call__.remote(_uninstall_in_replica),
                    timeout=60)
            except Exception:
                pass
    assert len(statuses) == 36
    assert statuses.count(200) >= 1, statuses
    # Every answer is HONEST: a completion, a fast 503 shed, or a 504
    # deadline — never a bare 500 or a client-side hang/timeout.
    assert all(s in (200, 503, 504) for s in statuses), statuses

    # ---- storm drains: every replica's pool back to baseline.
    deadline = time.monotonic() + 60
    pools = []
    while time.monotonic() < deadline:
        pools = [ray_tpu.get(r["actor"].handle_request.remote(
            "pool_stats", (), {}), timeout=30) for r in replicas.values()]
        if all(p["pinned"] == 0 and p["active_slots"] == 0
               and p["waiting"] == 0 and p["prefilling"] == 0
               for p in pools):
            break
        time.sleep(0.5)
    for p in pools:
        assert p["pinned"] == 0 and p["active_slots"] == 0, pools
        assert p["waiting"] == 0 and p["prefilling"] == 0, pools

    result = verifier.verify(baseline)
    assert result.ok, result.violations
    serve.shutdown()
