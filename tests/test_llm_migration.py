"""Disaggregated prefill/decode serving + live KV-page migration (round 11).

The object-manager idea applied to the KV cache: a prefill replica's
pages MOVE to a decode replica (chunked stream over a credit-based TCP
loop channel) instead of being recomputed, an affinity spill migrates
the group's hot pages instead of throwing them away (PR-10 residue b),
and refcount-0 trie pages evicted under pressure spill to host RAM and
restore on a later hit. Every path's acceptance bar is greedy BYTE
PARITY against full recompute, and every failure mode (pressure,
source death mid-migration) must degrade to a clean cold prefill.
"""

import dataclasses
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.llm.engine import InferenceEngine, Request
from ray_tpu.llm.migration import KVMigrationSource, receive_kv_stream
from ray_tpu.models.llama import PRESETS, forward, init_params


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


def _drain(eng, req):
    while not req.done:
        eng.step()


def test_prefill_only_retires_without_sampling(small_model):
    """A prefill_only request computes the prompt's KV, registers it in
    the trie, and retires with finish_reason 'prefilled' — no token is
    ever sampled, and pin_for_export keeps the pages refcounted until
    the exporter releases them."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    prompt = list(range(1, 20))
    r = Request("p", list(prompt), max_new_tokens=1,
                prefill_only=True, pin_for_export=True)
    eng.add_request(r)
    _drain(eng, r)
    assert r.finish_reason == "prefilled" and not r.generated
    assert r.export_pinned, "retire must pin pages for the exporter"
    # pages are registered: a follow-up maps them as ordinary hits
    b = Request("b", list(prompt), max_new_tokens=4)
    eng.add_request(b)
    _drain(eng, b)
    assert b.cached_prefix_tokens == 18
    assert b.generated == naive_greedy(params, cfg, prompt, 4)
    eng.release_export_pins(r)
    assert not r.export_pinned


def test_export_import_roundtrip_parity(small_model):
    """ISSUE 11 acceptance: byte-parity roundtrip of the page payload —
    full blocks AND the partial tail block — between two engines, for a
    uniform resend and a mid-tail divergence."""
    cfg, params = small_model
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    prompt = list(range(1, 20))  # 2 full pages + 3-row tail
    r = Request("p", list(prompt), max_new_tokens=1,
                prefill_only=True, pin_for_export=True)
    a.add_request(r)
    _drain(a, r)
    payload = a.export_prefix_kv(prompt)
    a.release_export_pins(r)
    assert payload["full_pages"] == 2 and payload["partial_len"] == 2
    assert payload["k"].shape[1] == 3  # 2 full + 1 tail page

    b = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    assert b.import_prefix_kv(payload) == 18
    assert b.metrics["kv_migrations_in"] == 1
    rb = Request("b", list(prompt), max_new_tokens=4)
    b.add_request(rb)
    _drain(b, rb)
    assert rb.cached_prefix_tokens == 18
    assert rb.generated == naive_greedy(params, cfg, prompt, 4)

    # Mid-tail divergence: the imported partial still COW-forks safely.
    div = prompt[:17] + [99, 98]
    rc = Request("c", list(div), max_new_tokens=4)
    b.add_request(rc)
    _drain(b, rc)
    assert rc.cached_prefix_tokens == 17
    assert rc.generated == naive_greedy(params, cfg, div, 4)

    # Duplicate import: already-resident links free straight back.
    free_before = len(b.allocator.free) + sum(
        1 for p in b.allocator.page_hash
        if b.allocator.refcount.get(p, 0) == 0)
    assert b.import_prefix_kv(a.export_prefix_kv(prompt)) == 18
    free_after = len(b.allocator.free) + sum(
        1 for p in b.allocator.page_hash
        if b.allocator.refcount.get(p, 0) == 0)
    assert free_after == free_before  # no pages leaked to duplicates


def test_import_under_pressure_falls_back_cold(small_model):
    """A reservation failure on import is a clean no-op: the payload is
    dropped, the metric counts it, and the request cold-prefills with
    full parity."""
    cfg, params = small_model
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    prompt = list(range(1, 20))
    r = Request("p", list(prompt), max_new_tokens=1,
                prefill_only=True, pin_for_export=True)
    a.add_request(r)
    _drain(a, r)
    payload = a.export_prefix_kv(prompt)
    a.release_export_pins(r)

    tiny = InferenceEngine(cfg, params, max_slots=2, max_len=64,
                           page_size=8, num_pages=2)
    assert tiny.import_prefix_kv(payload) == 0
    assert tiny.metrics["kv_import_failures"] == 1
    assert tiny.metrics["kv_pages_imported"] == 0
    short = prompt[:12]  # fits the 2-page pool
    rc = Request("c", list(short), max_new_tokens=3)
    tiny.add_request(rc)
    _drain(tiny, rc)
    assert rc.cached_prefix_tokens == 0
    assert rc.generated == naive_greedy(params, cfg, short, 3)


def test_streamed_migration_overlaps_prefill(small_model):
    """The migration source streams pages WHILE later chunks are still
    prefilling; the importer lands them chunk-by-chunk and the follow-up
    request decodes byte-identically."""
    cfg, params = small_model
    prompt = list(range(1, 40))  # 4 full pages + 7-row tail
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk_size=8)
    r = Request("p", list(prompt), max_new_tokens=1,
                prefill_only=True, pin_for_export=True)
    a.add_request(r)
    src = KVMigrationSource(a, r, chunk_pages=1)
    driver = threading.Thread(target=_drain, args=(a, r))
    driver.start()
    b = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    stats = receive_kv_stream(b, src.address, timeout_s=30)
    driver.join()
    src.close()
    assert stats["complete"] and stats["cached_tokens"] == 39, stats
    assert stats["pages"] == 5 and stats["bytes"] > 0
    rb = Request("b", list(prompt), max_new_tokens=4)
    b.add_request(rb)
    _drain(b, rb)
    assert rb.cached_prefix_tokens == 38  # match caps at len-1
    assert rb.generated == naive_greedy(params, cfg, prompt, 4)
    assert not r.export_pinned  # source released its pins


def test_source_death_mid_migration_imports_prefix(small_model):
    """Chaos: the source dies mid-stream (the channel drops exactly as a
    killed prefill replica's would). The importer keeps the contiguous
    prefix it received — a prefix of a valid chain is a valid chain —
    and the request cold-prefills only the rest, byte-identically."""
    cfg, params = small_model
    prompt = list(range(1, 40))
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk_size=8)
    r = Request("p", list(prompt), max_new_tokens=1,
                prefill_only=True, pin_for_export=True)
    a.add_request(r)
    src = KVMigrationSource(a, r, chunk_pages=1, _die_after_chunks=2)
    driver = threading.Thread(target=_drain, args=(a, r))
    driver.start()
    c = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    stats = receive_kv_stream(c, src.address, timeout_s=10)
    driver.join()
    assert not stats["complete"]
    assert 0 < stats["cached_tokens"] < 39, stats
    rc = Request("c", list(prompt), max_new_tokens=4)
    c.add_request(rc)
    _drain(c, rc)
    assert rc.cached_prefix_tokens == stats["cached_tokens"]
    assert rc.generated == naive_greedy(params, cfg, prompt, 4)
    # the dead source's engine still releases its export pins
    deadline = time.monotonic() + 10
    while r.export_pinned and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not r.export_pinned


def test_spill_stream_exports_cached_prefix(small_model):
    """PR-11 residue (b) closed: the spill pull rides the CHUNKED
    migration stream — a static KVMigrationSource over already-cached
    trie pages, wire-identical to the live handoff (full blocks, tail,
    end), with the pins released when the stream drains."""
    cfg, params = small_model
    prompt = list(range(1, 40))  # 4 full pages + 7-row tail
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    r = Request("prime", list(prompt), max_new_tokens=1)
    a.add_request(r)
    _drain(a, r)  # retire registers the chain in the trie
    src = KVMigrationSource.for_cached_prefix(a, prompt, chunk_pages=1)
    assert src is not None
    b = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    stats = receive_kv_stream(b, src.address, timeout_s=30)
    src.close()
    assert stats["complete"], stats
    # the trie match caps at len-1 (the last token's hidden state seeds
    # sampling), so 4 full pages + a 6-row tail = 38 tokens travel
    assert stats["cached_tokens"] == 38, stats
    rb = Request("b", list(prompt), max_new_tokens=4)
    b.add_request(rb)
    _drain(b, rb)
    assert rb.cached_prefix_tokens == 38
    assert rb.generated == naive_greedy(params, cfg, prompt, 4)
    # pins released: every exported page is refcount-0 cached again
    assert all(a.allocator.refcount.get(p, 0) == 0
               for p in a.allocator.page_hash)
    # nothing cached for an unknown prompt -> no stream
    assert KVMigrationSource.for_cached_prefix(a, [99, 98, 97]) is None


def test_spill_stream_source_death_serves_partial_plus_cold(small_model):
    """Regression (ISSUE 12 satellite): source death mid-SPILL-pull
    degrades exactly like the disaggregation path — the target keeps the
    contiguous prefix received, cold-prefills the suffix, and the
    output is byte-identical to a full recompute."""
    cfg, params = small_model
    prompt = list(range(1, 40))
    a = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    r = Request("prime", list(prompt), max_new_tokens=1)
    a.add_request(r)
    _drain(a, r)
    src = KVMigrationSource.for_cached_prefix(a, prompt, chunk_pages=1,
                                              _die_after_chunks=2)
    c = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    stats = receive_kv_stream(c, src.address, timeout_s=10)
    assert not stats["complete"]
    assert 0 < stats["cached_tokens"] < 38, stats
    rc = Request("c", list(prompt), max_new_tokens=4)
    c.add_request(rc)
    _drain(c, rc)
    assert rc.cached_prefix_tokens == stats["cached_tokens"]
    assert rc.generated == naive_greedy(params, cfg, prompt, 4)
    # the dying source still released its export pins
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and any(
            a.allocator.refcount.get(p, 0)
            for p in a.allocator.page_hash):
        time.sleep(0.05)
    assert all(a.allocator.refcount.get(p, 0) == 0
               for p in a.allocator.page_hash)


def test_tiered_kv_host_spill_and_restore(small_model):
    """Stretch (d): refcount-0 trie pages evicted under pressure spill
    to host RAM keyed by chain hash and restore on a later match_prefix
    hit instead of dying — with byte parity."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          num_pages=4, host_kv_cache_pages=8)
    first = list(range(1, 18))
    r1 = Request("x", list(first), max_new_tokens=1)
    eng.add_request(r1)
    _drain(eng, r1)
    # pressure: a second long prompt evicts x's cached pages
    r2 = Request("y", [50 + i for i in range(17)], max_new_tokens=1)
    eng.add_request(r2)
    _drain(eng, r2)
    assert eng.metrics["host_kv_spilled_pages"] > 0
    r3 = Request("x2", list(first), max_new_tokens=3)
    eng.add_request(r3)
    _drain(eng, r3)
    assert eng.metrics["host_kv_restored_pages"] > 0
    assert r3.cached_prefix_tokens >= 8  # ≥ one restored page
    assert r3.generated == naive_greedy(params, cfg, first, 3)
    # disabled tier spills nothing
    off = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          num_pages=4)
    assert off.allocator.on_evict is None


def test_router_ships_migrate_from_on_spill():
    """Router unit: a load-aware spill (and a saturation spill) reports
    the still-alive previous replica through spill_out; a repick of the
    affine replica or a dead one reports nothing."""
    from ray_tpu.core.config import get_config
    from ray_tpu.serve.router import Router

    from collections import OrderedDict

    cfg = get_config()
    saved = cfg.serve_affinity_spill_margin
    cfg.serve_affinity_spill_margin = 1
    try:
        class _A:  # stand-in actor with an id
            def __init__(self, b):
                self._actor_id = b

        ids = {"r1": b"\x01" * 8, "r2": b"\x02" * 8}
        router = Router.__new__(Router)
        router._key = "replicas::app::dep"
        router._lock = threading.Lock()
        router._cond = threading.Condition(router._lock)
        router._replicas = {rid: {"actor": _A(b), "max_ongoing": 8}
                            for rid, b in ids.items()}
        router._inflight = {"r1": 0, "r2": 0}
        router._model_affinity = {}
        router._group_affinity = OrderedDict()
        router.affinity_stats = {"hits": 0, "misses": 0, "spills": 0,
                                 "new_groups": 0}
        router.spill_migrations = 0
        router._init_overload_state()
        spill = {}
        first, _ = router.assign_replica(prefix_group="g", spill_out=spill)
        assert "migrate_from" not in spill  # new group: nothing to migrate
        router.release(first)
        other = "r2" if first == "r1" else "r1"
        with router._cond:
            router._inflight[first] += 2  # past margin 1
        spill = {}
        rid, _ = router.assign_replica(prefix_group="g", spill_out=spill)
        assert rid == other
        assert spill["migrate_from"] == first
        assert spill["actor_id"] == ids[first].hex()
        # dead previous replica: purged, no source shipped
        router.release(rid)
        router.remove_replica(other)  # the group's new affine dies
        spill = {}
        rid2, _ = router.assign_replica(prefix_group="g", spill_out=spill)
        assert rid2 == first and "migrate_from" not in spill
        router.release(rid2)
    finally:
        cfg.serve_affinity_spill_margin = saved


def test_disaggregated_serve_end_to_end(ray_cluster):
    """ISSUE 11 acceptance: a request admitted at a prefill replica
    streams its first token from a decode replica through the REAL
    proxy, pool membership shows in serve.status(), the response is
    byte-identical to a unified deployment's, and the handoff leaves an
    ``llm.kv_migrate`` span in the trace."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    try:
        serve.run(build_llm_app("debug-128", max_slots=4, max_len=128),
                  name="llm-uni", route_prefix="/uni")
        addr = serve.http_address()
        body = json.dumps({"prompt": "hello disaggregated world",
                           "max_tokens": 8}).encode()

        def post(path, data):
            req = urllib.request.Request(
                addr + path, data=data,
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=60)

        ref = json.loads(post("/uni/v1/completions", body).read())
        ref_text = ref["choices"][0]["text"]

        serve.run(build_llm_app("debug-128", max_slots=4, max_len=128,
                                serve_disaggregation="prefill_decode",
                                num_replicas=1, prefill_replicas=1),
                  name="llm-disagg", route_prefix="/dis")
        st = serve.status()["llm-disagg"]
        pools = {name: d.get("pool") for name, d in st.items()}
        assert pools == {"llm-decode": "decode", "llm-prefill": "prefill"}

        out = json.loads(post("/dis/v1/completions", body).read())
        assert out["choices"][0]["text"] == ref_text

        stream_body = json.dumps({"prompt": "hello disaggregated world",
                                  "max_tokens": 8, "stream": True}).encode()
        text = ""
        with post("/dis/v1/completions", stream_body) as resp:
            for line in resp:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    text += json.loads(line[6:])["choices"][0]["text"]
        assert text == ref_text

        # the handoff recorded llm.kv_migrate spans (flush ≈ every 5 s)
        from ray_tpu.util.state import list_spans

        deadline = time.monotonic() + 20
        spans = []
        while time.monotonic() < deadline and not spans:
            spans = [s for s in list_spans()
                     if s.get("name") == "llm.kv_migrate"
                     and s.get("attrs", {}).get("kind") == "disagg_handoff"]
            time.sleep(0.5)
        assert spans, "no llm.kv_migrate span reached the trace store"
        assert any(s["attrs"].get("cached_tokens", 0) > 0 for s in spans)
    finally:
        serve.shutdown()


def test_spill_migration_end_to_end(ray_cluster):
    """PR-10 residue (b) closed: with disaggregation OFF, an affinity
    spill's target imports the group's hot pages from the previous
    replica instead of cold-prefilling (router counter + engine
    metrics + byte parity)."""
    from ray_tpu import serve
    from ray_tpu.core.config import get_config
    from ray_tpu.llm import build_llm_app

    cfg = get_config()
    try:
        serve.run(build_llm_app("debug-128", num_replicas=2, max_slots=4,
                                max_len=256, page_size=16),
                  name="llm-spill", route_prefix="/spill")
        h = serve.get_app_handle("llm-spill").options(
            method_name="completions", prefix_group="grp-mig")
        prompt = "You are a helpful assistant. " * 4 + " tail"
        body = {"prompt": prompt, "max_tokens": 6}
        out1 = h.remote(body).result(timeout=60)
        router = h._get_router()
        affine = router._group_affinity["grp-mig"]
        bump = cfg.serve_affinity_spill_margin + 1
        with router._cond:
            router._inflight[affine] += bump
        try:
            out2 = h.remote(body).result(timeout=60)
        finally:
            with router._cond:
                router._inflight[affine] -= bump
        assert out2["choices"][0]["text"] == out1["choices"][0]["text"]
        assert router.spill_migrations == 1
        assert router._group_affinity["grp-mig"] != affine
        # the spill target's engine actually imported the pages
        m = h.options(method_name="engine_metrics",
                      prefix_group="grp-mig").remote().result(timeout=60)
        assert m["kv_migrations_in"] >= 1
        assert m["kv_pages_imported"] >= 1
    finally:
        serve.shutdown()


@pytest.mark.chaos
def test_prefill_replica_death_mid_migration_retries_cold(ray_cluster):
    """Chaos: kill the prefill replica while a handoff's migration
    stream is in flight. The client's retry must complete with correct
    bytes (served through the replacement prefill replica onto the
    decode pool) and the RecoveryVerifier must come back green."""
    from ray_tpu import serve
    from ray_tpu.chaos.verifier import RecoveryVerifier
    from ray_tpu.core.api import ActorHandle
    from ray_tpu.core.config import get_config
    from ray_tpu.llm import build_llm_app

    cfg = get_config()
    saved_chunk = cfg.kv_migration_chunk_pages
    cfg.kv_migration_chunk_pages = 1  # widen the mid-migration window
    verifier = RecoveryVerifier(timeout_s=60)
    baseline = verifier.snapshot_baseline()
    try:
        serve.run(build_llm_app("debug-128", max_slots=4, max_len=256,
                                page_size=16,
                                serve_disaggregation="prefill_decode",
                                num_replicas=1, prefill_replicas=1),
                  name="llm-chaos", route_prefix="/chaos")
        addr = serve.http_address()
        prompt = "c" * 180  # several chunks: the stream stays open a while
        body = json.dumps({"prompt": prompt, "max_tokens": 6,
                           "stream": True}).encode()

        def run_once(timeout=60.0):
            req = urllib.request.Request(
                addr + "/chaos/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            text = ""
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                for line in resp:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        text += json.loads(line[6:])["choices"][0]["text"]
            return text

        expected = run_once()  # healthy reference (also warms compiles)

        # resolve the prefill replica's actor from the routing table
        controller = ray_tpu.get_actor("SERVE_CONTROLLER")
        table = ray_tpu.get(controller.get_snapshot.remote(
            "replicas::llm-chaos::llm-prefill"), timeout=30)
        prefill_actor = ActorHandle(bytes.fromhex(table[0]["actor_id"]))

        # fire the request and kill the prefill replica mid-flight
        result: dict = {}

        def client():
            try:
                result["text"] = run_once()
            except Exception as e:
                result["error"] = f"{type(e).__name__}: {e}"

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.15)  # let admission + the migration stream begin
        ray_tpu.kill(prefill_actor)
        t.join(timeout=60)
        assert not t.is_alive()

        # Retry until the controller's replacement replica serves it.
        deadline = time.monotonic() + 120
        text, last_err = result.get("text"), result.get("error")
        while (text is None or text != expected) \
                and time.monotonic() < deadline:
            try:
                text = run_once(timeout=60.0)
            except Exception as e:
                last_err = f"{type(e).__name__}: {e}"
                time.sleep(1.0)
        assert text == expected, (text, last_err)
        result = verifier.verify(baseline)
        assert result.ok, result.violations
    finally:
        cfg.kv_migration_chunk_pages = saved_chunk
        serve.shutdown()
