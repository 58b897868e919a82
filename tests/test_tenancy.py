"""Multi-tenant LoRA multiplexing (round 16): one routing key across
header spellings, HBM adapter LRU with pinned-in-flight safety,
mixed-adapter decode in ONE dispatch, weighted-fair queueing, and
per-tenant quota/shed enforcement through the real proxy.

The regime under test: many tenants (adapters) share one replica fleet.
A noisy tenant's storm must shed ITS OWN work (fair-share preemption,
quota 429s with honest Retry-After) while a quiet tenant keeps its SLO;
a decode batch mixing distinct adapters must cost exactly the dispatches
of a single-adapter batch.
"""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import get_config
from ray_tpu.llm.engine import InferenceEngine, Request
from ray_tpu.llm.tenancy import (AdapterCapacityError, AdapterPool,
                                 QuotaExceeded, TenancyConfig, TenantLedger,
                                 TokenBucket, WeightedFairQueue, tenant_of)
from ray_tpu.models.llama import PRESETS, init_params
from ray_tpu.serve.multiplex import resolve_model_id
from ray_tpu.serve.router import RequestShed


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_adapter(cfg, rng, scale=0.5):
    """Random rank-2 adapter arrays for every attention projection."""
    L, E, H, KH, D = (cfg.n_layers, cfg.hidden, cfg.n_heads,
                      cfg.n_kv_heads, cfg.head_dim)
    r = 2
    dims = {"wq": (E, H * D), "wk": (E, KH * D), "wv": (E, KH * D),
            "wo": (H * D, E)}
    out = {}
    for p, (ein, eout) in dims.items():
        out[f"{p}.A"] = (rng.standard_normal((L, ein, r)) * scale / ein ** 0.5
                         ).astype(np.float32)
        out[f"{p}.B"] = (rng.standard_normal((L, r, eout)) * scale
                         ).astype(np.float32)
    return out


# ------------------------------------------------------------ adapter pool
def test_adapter_pool_evicts_lru_under_pressure():
    """The residency cap (max_loaded_adapters) triggers LRU eviction of
    the oldest UNPINNED adapter; stack capacity above the cap stays
    unused headroom."""
    pool = AdapterPool(capacity=4, max_resident=2)
    for aid in ("a", "b"):
        slot = pool.begin_load(aid)
        pool.commit_load(aid, 1.0)
        pool.unpin(aid)
        assert 1 <= slot <= 4
    assert list(pool.resident()) == ["a", "b"]
    # touching "a" refreshes its LRU position: "b" is now the victim
    assert pool.lookup("a") is not None
    pool.unpin("a")
    pool.begin_load("c")
    pool.commit_load("c", 1.0)
    pool.unpin("c")
    st = pool.stats()
    assert list(pool.resident()) == ["a", "c"]
    assert st["evictions"] == 1 and st["resident_count"] == 2
    assert st["max_resident"] == 2 and st["capacity"] == 4


def test_adapter_pool_pins_protect_inflight_adapters():
    """An adapter pinned by an in-flight request is never evicted: with
    every resident slot pinned, a cold load raises AdapterCapacityError
    (the engine turns that into admission deferral, not a failure)."""
    pool = AdapterPool(capacity=2)
    pool.begin_load("a")          # pinned by the load itself
    pool.begin_load("b")
    with pytest.raises(AdapterCapacityError):
        pool.begin_load("c")
    # a finishing request unpins -> the load proceeds by evicting "a"
    pool.unpin("a")
    slot_c = pool.begin_load("c")
    pool.commit_load("c", 1.0)
    assert "a" not in pool.resident() and slot_c >= 1
    assert pool.stats()["evictions"] == 1


def test_adapter_pool_reload_after_evict():
    """An evicted adapter re-loads into a fresh slot on next use (the
    hot-load path), and the loads counter records it."""
    pool = AdapterPool(capacity=1)
    pool.begin_load("a")
    pool.commit_load("a", 2.0)
    pool.unpin("a")
    pool.begin_load("b")          # evicts a
    pool.commit_load("b", 2.0)
    pool.unpin("b")
    assert pool.lookup("a") is None     # miss: caller must begin_load
    pool.begin_load("a")
    pool.commit_load("a", 2.0)
    st = pool.stats()
    assert list(pool.resident()) == ["a"]
    assert st["loads"] == 3 and st["evictions"] == 2


# ------------------------------------------------------------- quotas / wfq
def test_token_bucket_honest_retry_after():
    """A refused acquire reports WHEN the bucket will actually cover the
    request at the sustained rate — not a constant."""
    bucket = TokenBucket(rate=10.0, burst=50.0)
    ok, _ = bucket.try_acquire(50)
    assert ok
    ok, retry = bucket.try_acquire(30)
    assert not ok
    # deficit = 30 tokens at 10 tok/s -> ~3s (refill during the test can
    # shave a second off)
    assert 2 <= retry <= 3
    ok, retry = bucket.try_acquire(10)
    assert not ok and retry == 1


def test_ledger_quota_exceeded_carries_http_fields():
    cfg = TenancyConfig.from_dict(
        {"tenants": {"t": {"tokens_per_s": 5.0, "burst_tokens": 10.0}}})
    ledger = TenantLedger(cfg)
    ledger.admit("t", 10)
    with pytest.raises(QuotaExceeded) as ei:
        ledger.admit("t", 10)
    assert ei.value.http_status.startswith("429")
    assert ei.value.reason == "quota_exhausted"
    assert 1 <= ei.value.retry_after <= 60
    row = ledger.snapshot()["t"]
    assert row["admitted"] == 1 and row["quota_rejects"] == 1
    assert row["tokens_in"] == 10 and "quota_remaining" in row
    # unmetered tenants never raise
    ledger.admit("free", 10 ** 6)


def test_wfq_two_to_one_weights_admit_two_to_one():
    """ISSUE 16 satellite: under saturation (both tenants always have a
    waiter queued), a 2:1 weight split admits work in a 2:1 ratio."""
    wfq = WeightedFairQueue({"gold": 2.0, "bronze": 1.0})
    tickets = {"gold": [], "bronze": []}
    admitted = {"gold": 0, "bronze": 0}
    for t in ("gold", "bronze"):
        for _ in range(3):                       # standing backlog
            tickets[t].append(wfq.enqueue(t))
    for _ in range(300):
        head = next(tk for t in tickets for tk in tickets[t]
                    if wfq.is_head(tk))
        tenant = "gold" if head in tickets["gold"] else "bronze"
        wfq.complete(head)
        tickets[tenant].remove(head)
        admitted[tenant] += 1
        tickets[tenant].append(wfq.enqueue(tenant))   # stay saturated
    ratio = admitted["gold"] / admitted["bronze"]
    assert 1.8 <= ratio <= 2.2, admitted


def test_wfq_cancel_rolls_back_and_idle_share_flows():
    wfq = WeightedFairQueue({"a": 1.0, "b": 1.0})
    t1 = wfq.enqueue("a")
    t2 = wfq.enqueue("a")
    wfq.cancel(t2)        # shed: must not penalize a's next arrival
    t3 = wfq.enqueue("b")
    assert wfq.is_head(t1)
    wfq.complete(t1)
    assert wfq.is_head(t3)
    wfq.complete(t3)
    assert len(wfq) == 0
    # an idle tenant doesn't bank credit: after b worked alone, a's next
    # stamp starts at the current virtual clock, not at zero
    for _ in range(5):
        wfq.complete(wfq.enqueue("b"))
    ta = wfq.enqueue("a")
    tb = wfq.enqueue("b")
    assert wfq.is_head(ta) and not wfq.is_head(tb)
    wfq.complete(ta)
    wfq.complete(tb)


# ------------------------------------------------------------- routing key
def test_resolve_model_id_unifies_spellings():
    """Satellite: serve_multiplexed_model_id, x-raytpu-model, and the
    OpenAI body `model` field resolve to ONE routing key, in that
    precedence, case-insensitively."""
    assert resolve_model_id({"serve_multiplexed_model_id": "m1",
                             "x-raytpu-model": "m2"}, {"model": "m3"}) == "m1"
    assert resolve_model_id({"X-RayTPU-Model": "m2"}, {"model": "m3"}) == "m2"
    assert resolve_model_id({}, {"model": "m3"}) == "m3"
    assert resolve_model_id({}, {}) == ""
    assert resolve_model_id(None) == ""
    assert tenant_of("") == "default" and tenant_of("m1") == "m1"


# ------------------------------------------------------- engine mixed decode
def test_mixed_adapter_batch_one_dispatch_and_parity(small_model, tmp_path):
    """Tentpole (c): a decode batch mixing DISTINCT adapters produces
    byte-identical greedy tokens to serving the same requests
    sequentially, and consumes EXACTLY as many decode dispatches as a
    single-adapter batch of the same shape — decode cost must not scale
    with the number of distinct adapters."""
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter

    cfg, params = small_model
    rng = np.random.default_rng(16)
    for name in ("t1", "t2", "t3"):
        save_adapter(str(tmp_path / f"{name}.npz"), _make_adapter(cfg, rng))
    lora = LoRAServingConfig(max_loras=4, max_rank=4,
                             dynamic_lora_loading_path=str(tmp_path))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8],
               [1, 6, 1, 8, 0, 3], [5, 5, 5, 9, 7]]

    def run(models, concurrent):
        eng = InferenceEngine(cfg, params, max_slots=4, max_len=64,
                              lora_config=lora, enable_prefix_cache=False)
        assert eng.mixed_dispatch_enabled, \
            "a LoRA stack must no longer disable mixed dispatch"
        reqs = [Request(f"r{i}", p, max_new_tokens=6, model=m)
                for i, (p, m) in enumerate(zip(prompts, models))]
        d0 = eng.metrics["decode_dispatches"]
        for r in reqs:
            eng.add_request(r)
            if not concurrent:
                while not r.done:
                    eng.step()
        while any(not r.done for r in reqs):
            eng.step()
        return ([list(r.generated) for r in reqs],
                eng.metrics["decode_dispatches"] - d0)

    mix = [None, "t1", "t2", "t3"]
    batch_toks, _ = run(mix, concurrent=True)
    seq_toks, _ = run(mix, concurrent=False)
    assert batch_toks == seq_toks
    # dispatch-count flatness: same shapes, 3 distinct adapters vs 1
    _, mixed_d = run(["t1", "t2", "t3", "t1"], concurrent=True)
    _, single_d = run(["t1", "t1", "t1", "t1"], concurrent=True)
    assert mixed_d == single_d, (mixed_d, single_d)


def test_engine_defers_admission_when_adapters_pinned(small_model, tmp_path):
    """When every resident adapter slot is pinned by in-flight requests,
    a cold-adapter request DEFERS (head-of-line wait, adapter_defers
    metric) and completes once a slot unpins — never a client error."""
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter

    cfg, params = small_model
    rng = np.random.default_rng(3)
    for name in ("ad1", "ad2"):
        save_adapter(str(tmp_path / f"{name}.npz"), _make_adapter(cfg, rng))
    lora = LoRAServingConfig(max_loras=2, max_rank=4,
                             max_loaded_adapters=1,
                             dynamic_lora_loading_path=str(tmp_path))
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64,
                          lora_config=lora, enable_prefix_cache=False)
    r1 = Request("r1", [3, 1, 4, 1, 5], max_new_tokens=8, model="ad1")
    r2 = Request("r2", [2, 7, 1, 8], max_new_tokens=4, model="ad2")
    eng.add_request(r1)
    eng.step()                    # r1 admitted, ad1 pinned in the 1 slot
    eng.add_request(r2)
    deadline = time.monotonic() + 60
    while not (r1.done and r2.done):
        assert time.monotonic() < deadline
        eng.step()
    assert eng.metrics["adapter_defers"] >= 1
    assert len(r1.generated) == 8 and len(r2.generated) == 4
    assert list(eng.lora_manager.resident()) == ["ad2"]


# ------------------------------------------------------------- router units
def _bare_router(replicas: dict[str, int]):
    """Router skeleton for tenancy-policy unit tests (same shape as
    test_overload's): real assign/release/shed logic, no controller."""
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
    cfg = get_config()
    saved = (cfg.serve_max_queued_requests, cfg.serve_shed_policy)
    yield cfg
    cfg.serve_max_queued_requests, cfg.serve_shed_policy = saved


def test_router_quiet_tenant_jumps_noisy_backlog(overload_cfg):
    """WFQ at the router: a quiet tenant's first waiter lands near the
    HEAD of a noisy tenant's standing backlog (virtual start = current
    vclock), instead of behind it in arrival order."""
    overload_cfg.serve_max_queued_requests = 16
    router = _bare_router({"r1": 1})
    router.assign_replica()                      # saturate the only slot
    router._update_tenancy({"weights": {"quiet": 1.0, "noisy": 1.0}})
    admitted: list[str] = []
    alock = threading.Lock()

    def wait_one(tenant):
        try:
            router.assign_replica(timeout=30.0, model_id=tenant)
            with alock:
                admitted.append(tenant)
        except Exception:
            with alock:
                admitted.append(f"{tenant}-failed")

    threads = []
    for i in range(4):                           # noisy backlog first
        t = threading.Thread(target=wait_one, args=("noisy",), daemon=True)
        t.start()
        threads.append(t)
    deadline = time.monotonic() + 5
    while router.overload_snapshot()["queued"] < 4:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    t = threading.Thread(target=wait_one, args=("quiet",), daemon=True)
    t.start()
    threads.append(t)
    deadline = time.monotonic() + 5
    while router.overload_snapshot()["queued"] < 5:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    for _ in range(5):                           # serve them one by one
        router.release("r1")
        n = len(admitted)
        deadline = time.monotonic() + 10
        while len(admitted) == n:
            assert time.monotonic() < deadline
            time.sleep(0.005)
    for t in threads:
        t.join(timeout=10)
    # the quiet waiter arrived LAST but is admitted within the first two
    # slots (its virtual finish time ties the noisy head's, ticket order
    # breaks the tie) — strict FIFO would admit it fifth.
    assert "quiet" in admitted[:2], admitted
    assert all(not a.endswith("failed") for a in admitted)


def test_router_fair_share_shed_prefers_noisy_waiter(overload_cfg):
    """Tenant-aware shedding: a full queue held by one tenant gives a
    slot to an under-share tenant by preempting the NOISY tenant's
    newest waiter — and a single-tenant flood still sheds the incoming
    request (queue_full), exactly the pre-tenancy behavior."""
    overload_cfg.serve_max_queued_requests = 2
    overload_cfg.serve_shed_policy = "cost"
    router = _bare_router({"r1": 1})
    router.assign_replica()
    outcomes: dict[str, list] = {"noisy": [], "quiet": []}
    olock = threading.Lock()

    def wait_one(tenant):
        try:
            r = router.assign_replica(timeout=20.0, model_id=tenant)
            with olock:
                outcomes[tenant].append(r)
        except Exception as e:
            with olock:
                outcomes[tenant].append(e)

    threads = [threading.Thread(target=wait_one, args=("noisy",),
                                daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5
    while router.overload_snapshot()["queued"] < 2:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    # same-tenant overflow: incoming noisy request is shed, waiters stay
    with pytest.raises(RequestShed) as ei:
        router.assign_replica(timeout=10.0, model_id="noisy")
    assert ei.value.reason == "queue_full"
    # under-share quiet tenant: preempts the newest noisy waiter instead
    tq = threading.Thread(target=wait_one, args=("quiet",), daemon=True)
    tq.start()
    threads.append(tq)
    deadline = time.monotonic() + 10
    while not any(isinstance(o, RequestShed) for o in outcomes["noisy"]):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    shed = next(o for o in outcomes["noisy"] if isinstance(o, RequestShed))
    assert shed.reason == "preempted"
    snap = router.overload_snapshot()
    assert snap["shed_by_tenant"].get("noisy") == 2
    assert "quiet" not in snap["shed_by_tenant"]
    # drain one slot at a time: quiet + the surviving noisy waiter both
    # get served
    for _ in range(2):
        served = sum(1 for outs in outcomes.values() for o in outs
                     if not isinstance(o, Exception))
        router.release("r1")
        deadline = time.monotonic() + 10
        while sum(1 for outs in outcomes.values() for o in outs
                  if not isinstance(o, Exception)) == served:
            assert time.monotonic() < deadline
            time.sleep(0.005)
    for t in threads:
        t.join(timeout=15)
    assert len(outcomes["quiet"]) == 1 \
        and not isinstance(outcomes["quiet"][0], Exception)


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


def test_multiplex_header_unification_e2e(serve_instance):
    """Satellite: all three routing-key spellings reach the replica as
    the SAME multiplexed model id through the real proxy."""

    @serve.deployment(num_replicas=1)
    class Echo:
        def __call__(self, request):
            from ray_tpu.serve.multiplex import get_multiplexed_model_id

            return {"model_id": get_multiplexed_model_id()}

    serve.run(Echo.bind(), name="mux", route_prefix="/mux")
    addr = serve.http_address()
    for headers, body in (
        ({"serve_multiplexed_model_id": "m1"}, {}),
        ({"x-raytpu-model": "m1"}, {}),
        ({"X-RayTPU-Model": "m1"}, {}),
        ({}, {"model": "m1"}),
    ):
        status, raw, _h = _post(addr, "/mux", body, headers=headers)
        assert status == 200, (headers, body, status)
        assert json.loads(raw)["model_id"] == "m1", (headers, body)
    serve.delete("mux")


def test_quota_429_and_tenant_rows_e2e(serve_instance):
    """Tentpole (d) e2e: a quota-exhausted tenant gets an honest 429 +
    Retry-After through the real proxy (SSE error envelope), the quiet
    tenant rides on untouched, and the per-tenant rows reach
    serve.status() via the controller probe path."""
    from ray_tpu.llm import build_llm_app

    app = build_llm_app(
        "debug-128", max_slots=4, max_len=128, page_size=16,
        prefill_chunk_size=64, num_replicas=1, max_ongoing_requests=8,
        tenancy_config={"tenants": {
            # one request's worth of burst and a refill that covers a
            # second only after 72 s: however long a loaded host takes
            # over the first request, the next one is refused
            "metered": {"tokens_per_s": 0.25, "burst_tokens": 24.0},
            "free": {"weight": 2.0},
        }})
    serve.run(app, name="quota", route_prefix="/quota", timeout_s=60.0)
    addr = serve.http_address()
    body = {"prompt": "hello quota world", "max_tokens": 4}
    status, raw, _h = _post(addr, "/quota/v1/completions", body,
                            headers={"x-raytpu-model": "metered"},
                            timeout=60.0)
    assert status == 200, raw[:200]
    # burst exhausted (cost ≈ 17 prompt + 4 gen ≈ 21 of the 24-token
    # burst): the second/third request cannot be covered
    saw_429 = None
    for _ in range(3):
        status, raw, h = _post(addr, "/quota/v1/completions", body,
                               headers={"x-raytpu-model": "metered"},
                               timeout=60.0)
        if status == 429:
            saw_429 = h
            break
    assert saw_429 is not None, "quota never produced a 429"
    retry = int(saw_429.get("Retry-After", "0"))
    # honest: a deficit of up to 18 tokens at 0.25 tok/s, never the constant 1
    assert 2 <= retry <= 60, retry
    # the quiet tenant is untouched by the metered tenant's quota
    status, _raw, _h = _post(addr, "/quota/v1/completions", body,
                             headers={"x-raytpu-model": "free"},
                             timeout=60.0)
    assert status == 200
    # per-tenant rows reach serve.status() through the probe fold
    deadline = time.monotonic() + 45
    tenants = {}
    while time.monotonic() < deadline:
        st = serve.status().get("quota", {})
        slot = next(iter(st.values()), {})
        tenants = (slot.get("tenancy") or {}).get("tenants") or {}
        if "metered" in tenants and "free" in tenants:
            break
        time.sleep(1.0)
    assert tenants.get("metered", {}).get("quota_rejects", 0) >= 1
    assert tenants["metered"]["admitted"] >= 1
    assert "quota_remaining" in tenants["metered"]
    assert tenants["free"]["admitted"] >= 1 \
        and "quota_remaining" not in tenants["free"]
    serve.delete("quota")


def test_tenant_aware_shed_quiet_tenant_clean_e2e(serve_instance):
    """Satellite: through the real proxy, a noisy tenant's flood over
    the router queue bound sheds NOISY waiters; the quiet tenant's
    requests all return 200 (quiet 503 rate ~ 0). The bound lives in
    the PROXY process, so it is tuned through its live-config seam."""

    @serve.deployment(num_replicas=1, max_ongoing_requests=1)
    class Slow:
        def __call__(self, request):
            time.sleep(0.25)
            return {"ok": True}

    saved = None
    proxy = None
    try:
        serve.run(Slow.bind(), name="shed", route_prefix="/shed")
        addr = serve.http_address()
        proxy = ray_tpu.get_actor("SERVE_PROXY")
        saved = ray_tpu.get(proxy.apply_config.remote(
            {"serve_max_queued_requests": 2}), timeout=30)
        results = {"noisy": [], "quiet": []}
        rlock = threading.Lock()

        def client(tenant, n):
            for _ in range(n):
                status, _raw, _h = _post(
                    addr, "/shed", {}, headers={"x-raytpu-model": tenant},
                    timeout=60.0)
                with rlock:
                    results[tenant].append(status)

        noisy = [threading.Thread(target=client, args=("noisy", 4),
                                  daemon=True) for _ in range(4)]
        for t in noisy:
            t.start()
        time.sleep(0.3)                  # let the flood fill the queue
        quiet = threading.Thread(target=client, args=("quiet", 3),
                                 daemon=True)
        quiet.start()
        quiet.join(timeout=60)
        for t in noisy:
            t.join(timeout=60)
        assert results["quiet"] == [200, 200, 200], results["quiet"]
        assert any(s == 503 for s in results["noisy"]), results["noisy"]
    finally:
        if proxy is not None and saved:
            ray_tpu.get(proxy.apply_config.remote(saved), timeout=30)
        serve.delete("shed")


def test_wfq_token_cost_equalizes_skewed_request_sizes():
    """ISSUE 18 satellite: WFQ charges ESTIMATED TOKENS, not 1.0 per
    request. With equal weights, a tenant sending 100x-larger requests
    admits ~100x fewer of them — the admitted TOKEN throughput is what
    equalizes. (Under the old cost=1.0 charging, request counts
    equalized and the big tenant took ~100x the token share.)"""
    wfq = WeightedFairQueue({"big": 1.0, "small": 1.0})
    cost = {"big": 400.0, "small": 4.0}
    tickets = {"big": [], "small": []}
    admitted_tok = {"big": 0.0, "small": 0.0}
    admitted_req = {"big": 0, "small": 0}
    for t in ("big", "small"):
        for _ in range(3):                       # standing backlog
            tickets[t].append(wfq.enqueue(t, cost=cost[t]))
    for _ in range(606):
        head = next(tk for t in tickets for tk in tickets[t]
                    if wfq.is_head(tk))
        tenant = "big" if head in tickets["big"] else "small"
        wfq.complete(head)
        tickets[tenant].remove(head)
        admitted_req[tenant] += 1
        admitted_tok[tenant] += cost[tenant]
        tickets[tenant].append(wfq.enqueue(tenant, cost=cost[tenant]))
    tok_ratio = admitted_tok["big"] / admitted_tok["small"]
    assert 0.8 <= tok_ratio <= 1.25, admitted_tok
    req_ratio = admitted_req["small"] / admitted_req["big"]
    assert 80 <= req_ratio <= 125, admitted_req


def test_ledger_cost_correction_ewma_and_clamp():
    """Retire-time correction: tenants that systematically stop far
    short of max_tokens get their estimates scaled DOWN (EWMA of
    actual/estimated, clamped to [0.01, 100])."""
    ledger = TenantLedger(TenancyConfig.from_dict(
        {"tenants": {"early-stopper": {}}}))
    ledger.note_actual("early-stopper", estimated=1000.0, actual=100.0)
    row = ledger.snapshot()["early-stopper"]
    assert row["cost_correction"] == 0.1       # first sample sets it
    for _ in range(40):
        ledger.note_actual("early-stopper", estimated=1000.0, actual=100.0)
    row = ledger.snapshot()["early-stopper"]
    assert abs(row["cost_correction"] - 0.1) < 0.01   # EWMA converges
    ledger.note_actual("early-stopper", estimated=1.0, actual=10_000.0)
    st = ledger._tenants["early-stopper"]
    assert st.cost_ratio <= 100.0              # clamp survives outliers
    ledger.note_actual("early-stopper", estimated=0.0, actual=5.0)  # no-op


def test_ledger_slo_burn_tracks_breaches_and_recovers():
    """ttft_slo_ms: note_ttft returns True on breach, the burn fraction
    is windowed (recovers as healthy samples roll the window), and the
    snapshot row carries slo fields only for tenants WITH an SLO."""
    ledger = TenantLedger(TenancyConfig.from_dict(
        {"tenants": {"slo": {"ttft_slo_ms": 100.0}, "free": {}}}))
    assert ledger.note_ttft("slo", 250.0) is True
    assert ledger.note_ttft("slo", 50.0) is False
    assert ledger.note_ttft("free", 10_000.0) is False  # no SLO, no breach
    assert ledger.slo_burn_frac("slo") == 0.5
    for _ in range(6):
        ledger.note_ttft("slo", 50.0)
    assert ledger.slo_burn_frac("slo") == 1 / 8
    rows = ledger.snapshot()
    assert rows["slo"]["ttft_slo_ms"] == 100.0
    assert rows["slo"]["slo_breaches"] == 1
    assert rows["slo"]["slo_burn_frac"] == round(1 / 8, 4)
    assert "slo_burn_frac" not in rows["free"]
    assert ledger.slo_burn_frac("free") == 0.0


# ----------------------- round 19: HBM-slot accounting + live reweight
def test_adapter_pool_explicit_evict_accounting():
    """Satellite: an explicit eviction returns the slot to the FREE list
    (not merely the recyclable pool), fires the device-release hook, and
    counts as a device_unload — while pinned adapters stay untouchable."""
    pool = AdapterPool(capacity=4)
    fired = []
    pool.on_evict = lambda aid, slot: fired.append((aid, slot))
    for aid in ("a", "b"):
        pool.begin_load(aid)
        pool.commit_load(aid, 1.0)
        pool.unpin(aid)
    st0 = pool.stats()
    assert st0["free_slots"] == 2 and st0["device_unloads"] == 0
    slot = pool.evict("a")
    assert slot is not None and fired == [("a", slot)]
    st = pool.stats()
    assert st["free_slots"] == 3 and st["device_unloads"] == 1
    assert list(pool.resident()) == ["b"]
    assert pool.evict("missing") is None
    pool.begin_load("c")                 # pinned by the in-flight load
    assert pool.evict("c") is None
    assert pool.stats()["device_unloads"] == 1


def test_adapter_pool_evict_idle_skips_pinned():
    """evict_idle (the scale-to-zero HBM reclaim) releases every
    UNPINNED adapter and leaves in-flight ones resident."""
    pool = AdapterPool(capacity=4)
    for aid in ("a", "b", "c"):
        pool.begin_load(aid)
        pool.commit_load(aid, 1.0)
    pool.unpin("a")
    pool.unpin("b")                      # "c" stays pinned
    released = pool.evict_idle()
    assert sorted(aid for aid, _ in released) == ["a", "b"]
    st = pool.stats()
    assert st["free_slots"] == 3 and st["device_unloads"] == 2
    assert list(pool.resident()) == ["c"]


def test_lora_manager_unload_idle_zeroes_device_slot(small_model, tmp_path):
    """Satellite: unloading an idle adapter actually zeroes its device
    stack slot (HBM holds the identity adapter again, not stale weights)
    and the slot accounting shows the release; the adapter hot-reloads
    cleanly on next use."""
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter

    cfg, params = small_model
    rng = np.random.default_rng(7)
    save_adapter(str(tmp_path / "ad1.npz"), _make_adapter(cfg, rng))
    lora = LoRAServingConfig(max_loras=2, max_rank=4,
                             dynamic_lora_loading_path=str(tmp_path))
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64,
                          lora_config=lora, enable_prefix_cache=False)
    r = Request("r1", [3, 1, 4, 1, 5], max_new_tokens=4, model="ad1")
    eng.add_request(r)
    while not r.done:
        eng.step()
    (aid, slot), = eng.lora_manager.resident().items()
    assert aid == "ad1"
    stack = eng.executor.lora_stack
    assert any(np.asarray(stack[k][:, slot]).any() for k in stack), \
        "adapter install left the stack slot empty"
    assert eng.lora_manager.unload_idle() == 1
    stack = eng.executor.lora_stack
    for k in stack:
        assert not np.asarray(stack[k][:, slot]).any(), \
            f"{k} slot {slot} still holds weights after unload"
    st = eng.lora_manager.stats()
    assert st["device_unloads"] == 1 and st["resident_count"] == 0
    assert st["free_slots"] == 2
    r2 = Request("r2", [3, 1, 4, 1, 5], max_new_tokens=4, model="ad1")
    eng.add_request(r2)
    while not r2.done:
        eng.step()
    assert list(eng.lora_manager.resident()) == ["ad1"]
    assert list(r2.generated) == list(r.generated)


def test_live_wfq_reweight_midrun_e2e(serve_instance):
    """Satellite: serve.update_tenancy_config flips tenant WFQ weights
    MID-RUN — the controller re-publishes the ``tenancy::`` long-poll
    key, a live router picks the new shares up without a redeploy, and
    the same replica keeps serving."""
    from ray_tpu.llm import build_llm_app
    from ray_tpu.serve.router import Router

    app = build_llm_app(
        "debug-128", max_slots=2, max_len=64, page_size=8,
        prefill_chunk_size=32, num_replicas=1, max_ongoing_requests=4,
        tenancy_config={"tenants": {"gold": {"weight": 3.0},
                                    "free": {"weight": 1.0}}})
    serve.run(app, name="wfq", route_prefix="/wfq", timeout_s=60.0)
    addr = serve.http_address()
    body = {"prompt": "hello weights", "max_tokens": 4}
    status, raw, _h = _post(addr, "/wfq/v1/completions", body, timeout=60.0)
    assert status == 200, raw[:200]

    router = Router("wfq", "LLMDeployment")  # live, like the proxy's
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not router._tenant_weights:
            time.sleep(0.2)
        assert router._tenant_weights == {"gold": 3.0, "free": 1.0}

        out = serve.update_tenancy_config(
            {"tenants": {"gold": {"weight": 8.0}, "free": {"weight": 1.0}}},
            app_name="wfq")
        assert out["updated"] == ["LLMDeployment"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline \
                and router._tenant_weights.get("gold") != 8.0:
            time.sleep(0.2)
        assert router._tenant_weights == {"gold": 8.0, "free": 1.0}
        # No redeploy: the same single replica answers after the flip.
        status, raw, _h = _post(addr, "/wfq/v1/completions", body,
                                timeout=60.0)
        assert status == 200, raw[:200]
        st = next(iter(serve.status().get("wfq", {}).values()), {})
        assert st.get("running_replicas") == 1
    finally:
        router._long_poll.stop()
    serve.delete("wfq")
