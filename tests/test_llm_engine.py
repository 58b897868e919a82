"""The continuous-batching engine against the plain forward: paged KV cache
with static-shape block tables, chunked prefill, prefix caching and its page
allocator, LoRA slots, and the mixed prefill/decode schedule. The OpenAI
routes over it: ``tests/test_llm.py``; tp and pp meshes:
``tests/test_llm_meshes.py``."""

import numpy as np

from llm_cases import _make_adapter, _merge_adapter, naive_greedy, small_model  # noqa: F401
from ray_tpu.llm.engine import InferenceEngine, Request


def test_cached_decode_matches_full_forward(small_model):
    """Slot-cache decode must be token-identical to recomputing the full
    forward each step (greedy)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64)
    prompts = [[1, 5, 9], [2, 4, 6, 8, 10, 12, 14], [3], list(range(1, 34))]
    reqs = [Request(f"r{i}", p, max_new_tokens=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    while any(not r.done for r in reqs):
        eng.step()
    for r, p in zip(reqs, prompts):
        assert r.generated == naive_greedy(params, cfg, p, 6), r.request_id


def test_continuous_batching_oversubscribed(small_model):
    """More requests than slots: finished sequences free slots for waiting
    requests; every request completes with the right number of tokens."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64)
    reqs = [Request(f"r{i}", [i + 1, i + 2], max_new_tokens=4) for i in range(7)]
    for r in reqs:
        eng.add_request(r)
    steps = 0
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
        assert steps < 500
    for r in reqs:
        assert len(r.generated) == 4
        assert r.finish_reason == "length"
    assert len(eng._free_slots) == 2 and not eng._active


def test_late_arrival_joins_running_batch(small_model):
    """A request added mid-decode is admitted without disturbing running
    sequences (continuous batching, not static batching)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64)
    first = Request("first", [1, 2, 3], max_new_tokens=10)
    eng.add_request(first)
    for _ in range(4):
        eng.step()
    late = Request("late", [7, 8], max_new_tokens=3)
    eng.add_request(late)
    while not (first.done and late.done):
        eng.step()
    assert first.generated == naive_greedy(params, cfg, [1, 2, 3], 10)
    assert late.generated == naive_greedy(params, cfg, [7, 8], 3)


def test_eos_and_cancel(small_model):
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64)
    # eos: pick the model's actual first greedy token as the eos id
    first_token = naive_greedy(params, cfg, [5, 6], 1)[0]
    r = Request("eos", [5, 6], max_new_tokens=10, eos_id=first_token)
    eng.add_request(r)
    while not r.done:
        eng.step()
    assert r.finish_reason == "stop" and len(r.generated) == 1

    r2 = Request("cancel", [1, 2], max_new_tokens=100)
    eng.add_request(r2)
    eng.step()
    eng.cancel("cancel")
    assert r2.done and r2.finish_reason == "cancelled"
    assert len(eng._free_slots) == 2

    # Cancelling a request still in the waiting queue must mark it done too
    # (a blocked caller would otherwise wait forever).
    r3 = Request("queued", [9], max_new_tokens=5)
    eng.add_request(r3)
    eng.cancel("queued")
    assert r3.done and r3.finish_reason == "cancelled"
    assert not eng.has_work


def test_chunked_prefill_parity(small_model):
    """A prompt spanning several prefill chunks must decode identically to
    the full forward (chunk attention over previously-written pages)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          prefill_chunk_size=16)
    prompt = list(range(1, 40))  # 39 tokens -> chunks 16+16+8
    r = Request("chunked", prompt, max_new_tokens=5)
    eng.add_request(r)
    while not r.done:
        eng.step()
    assert eng.metrics["prefill_chunks"] >= 3
    assert r.generated == naive_greedy(params, cfg, prompt, 5)


def test_prefix_cache_reuse(small_model):
    """A repeated prompt prefix reuses cached pages (no recompute) and
    still decodes identically."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    prompt = list(range(1, 20))  # 19 tokens -> 2 full pages cacheable
    a = Request("a", prompt, max_new_tokens=4)
    eng.add_request(a)
    while not a.done:
        eng.step()
    assert eng.metrics["prefix_hit_pages"] == 0
    b = Request("b", list(prompt), max_new_tokens=4)
    eng.add_request(b)
    while not b.done:
        eng.step()
    assert eng.metrics["prefix_hit_pages"] == 2
    assert b.generated == a.generated == naive_greedy(params, cfg, prompt, 4)


def test_cancel_mid_prefill_does_not_poison_prefix_cache(small_model):
    """Cancelling during chunked prefill must only prefix-register pages
    whose K/V was actually computed — a later identical prompt must not
    attend over garbage pages."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          prefill_chunk_size=8)
    prompt = list(range(1, 30))  # 29 tokens -> 4 chunks of 8
    r = Request("x", prompt, max_new_tokens=4)
    eng.add_request(r)
    eng.step()  # admit + prefill first chunk only
    assert r.prefill_pos == 8 and not r.done
    eng.cancel("x")
    r2 = Request("y", list(prompt), max_new_tokens=4)
    eng.add_request(r2)
    while not r2.done:
        eng.step()
    assert eng.metrics["prefix_hit_pages"] <= 1  # only the computed page
    assert r2.generated == naive_greedy(params, cfg, prompt, 4)


def test_a_model_name_on_an_engine_without_adapters_ends_in_an_event(small_model):
    """``step()`` returns a terminal event for a request that admission
    settles (here: a model name and no adapter manager), so whoever waits
    on events hears of it; its pages are back and the next request runs."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64)
    free = (eng.allocator.available(), len(eng._free_slots))
    lost = Request("lost", [1, 2, 3], max_new_tokens=4, model="tone")
    kept = Request("kept", [1, 2, 3], max_new_tokens=4)
    eng.add_request(lost)
    eng.add_request(kept)
    events = eng.step()
    assert [e for e in events if e["request_id"] == "lost"] == [
        {"request_id": "lost", "token": -1, "done": True,
         "finish_reason": "admission_failed"}]
    assert lost.done and not lost.generated and not lost.block_table
    assert eng.metrics["admission_failed"] == 1
    while not kept.done:
        eng.step()
    assert kept.generated == naive_greedy(params, cfg, [1, 2, 3], 4)
    assert (eng.allocator.available(), len(eng._free_slots)) == free and not eng.has_work


def test_page_pool_admission_control(small_model):
    """With a tiny page pool, admission waits for pages instead of
    corrupting running sequences; everything still completes."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          num_pages=8, enable_prefix_cache=False)
    # Each request needs ceil((6+20)/8)=4 pages; pool of 8 fits 2 at a time.
    reqs = [Request(f"r{i}", [i + 1] * 6, max_new_tokens=20) for i in range(5)]
    for r in reqs:
        eng.add_request(r)
    steps = 0
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
        assert steps < 2000
    for r in reqs:
        assert len(r.generated) == 20
    assert len(eng.allocator.free) == 8  # every page returned


def test_paged_attention_engine_greedy_parity(small_model):
    """The Pallas paged-attention decode kernel (attention_impl="paged",
    interpreted off-TPU) must be token-identical to the dense gather path
    under greedy decoding — the engine-level guarantee behind flipping
    the kernel on for TPU serving (ops/paged_attention.py)."""
    cfg, params = small_model
    prompts = [[1, 5, 9], [2, 4, 6, 8, 10, 12, 14], list(range(1, 34))]

    def run(attention_impl):
        eng = InferenceEngine(cfg, params, max_slots=4, max_len=64,
                              attention_impl=attention_impl)
        reqs = [Request(f"r{i}", p, max_new_tokens=6) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while any(not r.done for r in reqs):
            eng.step()
        return [r.generated for r in reqs]

    assert run("paged") == run("dense")


def test_lora_mixed_batch_matches_merged_weights(small_model, tmp_path):
    """Multi-LoRA serving: a decode batch mixing the base model and two
    adapters must produce, per request, exactly the tokens of an engine
    whose weights have that adapter merged in (greedy). This is the
    capability the reference gets from vLLM's multi-LoRA kernels
    (lora_model_loader.py + per-request `model` routing)."""
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter

    cfg, params = small_model
    rng = np.random.default_rng(7)
    ad1 = _make_adapter(cfg, rng)
    ad2 = _make_adapter(cfg, rng)
    save_adapter(str(tmp_path / "ad1.npz"), ad1)
    save_adapter(str(tmp_path / "ad2.npz"), ad2)

    prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    def run_engine(params_, model=None, lora=None):
        eng = InferenceEngine(cfg, params_, max_slots=4, max_len=64,
                              lora_config=lora)
        reqs = [Request(f"r{i}", prompt, max_new_tokens=6, model=m)
                for i, m in enumerate([model] if lora is None
                                      else [None, "ad1", "ad2"])]
        for r in reqs:
            eng.add_request(r)
        while any(not r.done for r in reqs):
            eng.step()
        return [r.generated for r in reqs]

    lora = LoRAServingConfig(max_loras=2, max_rank=4,
                             dynamic_lora_loading_path=str(tmp_path))
    base_toks, ad1_toks, ad2_toks = run_engine(params, lora=lora)

    assert base_toks == run_engine(params)[0], "identity slot changed base"
    assert ad1_toks == run_engine(_merge_adapter(cfg, params, ad1))[0]
    assert ad2_toks == run_engine(_merge_adapter(cfg, params, ad2))[0]
    assert ad1_toks != ad2_toks  # the adapters actually do something


def test_lora_lru_eviction_and_prefix_isolation(small_model, tmp_path):
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter

    cfg, params = small_model
    rng = np.random.default_rng(11)
    save_adapter(str(tmp_path / "a.npz"), _make_adapter(cfg, rng))
    save_adapter(str(tmp_path / "b.npz"), _make_adapter(cfg, rng))
    eng = InferenceEngine(
        cfg, params, max_slots=2, max_len=64,
        lora_config=LoRAServingConfig(max_loras=1, max_rank=4,
                                      dynamic_lora_loading_path=str(tmp_path)))
    prompt = list(range(1, 9))

    def run(model):
        r = Request(f"r-{model}-{np.random.randint(1e9)}", prompt,
                    max_new_tokens=4, model=model)
        eng.add_request(r)
        while not r.done:
            eng.step()
        return r.generated

    a1 = run("a")
    b1 = run("b")   # evicts a (max_loras=1)
    a2 = run("a")   # reloads a
    base = run(None)
    assert a1 == a2, "adapter a changed across LRU reload"
    assert a1 != b1 and a1 != base
    # prefix cache must be adapter-scoped: same prompt, different model,
    # yet outputs stayed adapter-faithful above (a2 == a1 after b ran
    # with the identical prompt proves no cross-adapter KV reuse).
    assert eng.metrics["prefix_hit_pages"] >= 0


def test_page_allocator_lru_eviction_order():
    """ISSUE 7 satellite: among refcount-0 cached pages the LRU victim is
    evicted first, and eviction unregisters the page's prefix hash."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(4)
    pages = alloc.alloc(4)
    assert pages is not None and not alloc.free
    # release all four into the prefix cache with distinct LRU stamps
    # (monotonic stamps: release order == recency order)
    for i, pid in enumerate(pages):
        alloc.register_prefix(pid, b"h%d" % i)
        alloc.release(pid)
    assert alloc.available() == 4 and not alloc.free  # all cached, evictable
    # allocation under pressure evicts in LRU order: pages[0] first
    (fresh,) = alloc.alloc(1)
    assert fresh == pages[0]
    assert alloc.lookup_prefix(b"h0") is None       # hash unregistered
    assert alloc.lookup_prefix(b"h1") == pages[1]   # newer entries intact
    (fresh2,) = alloc.alloc(1)
    assert fresh2 == pages[1]


def test_page_allocator_refcount_roundtrip():
    """register_prefix + share/release refcounting: a cached page revives
    through lookup, is pinned while shared, and only becomes evictable at
    refcount 0."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(2)
    (pid,) = alloc.alloc(1)
    alloc.register_prefix(pid, b"hash")
    alloc.release(pid)                      # cached, refcount 0
    assert alloc.lookup_prefix(b"hash") == pid
    alloc.share(pid)                        # a second sequence adopts it
    alloc.share(pid)
    assert alloc.refcount[pid] == 2
    # pinned: eviction must never pick it, so only the 1 free page remains
    assert alloc.available() == 1
    got = alloc.alloc(2)
    assert got is None                      # pool under pressure, pin holds
    alloc.release(pid)
    assert alloc.refcount[pid] == 1 and alloc.available() == 1
    alloc.release(pid)                      # back to cached-evictable
    assert alloc.available() == 2
    got = alloc.alloc(2)                    # now eviction may claim it
    assert got is not None and pid in got
    assert alloc.lookup_prefix(b"hash") is None


def test_page_allocator_alloc_under_pressure_prefers_free():
    """alloc() takes free pages before evicting cached ones, and a
    non-prefix page releases back to the free list (not the cache)."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(3)
    a, b = alloc.alloc(2)
    alloc.register_prefix(a, b"ha")
    alloc.release(a)          # cached
    alloc.release(b)          # plain free
    assert b in alloc.free and a not in alloc.free
    got = alloc.alloc(2)      # 2 free pages available: no eviction needed
    assert got is not None
    assert alloc.lookup_prefix(b"ha") == a  # cache entry survived
    (third,) = alloc.alloc(1)               # now eviction must claim `a`
    assert third == a and alloc.lookup_prefix(b"ha") is None


def test_page_allocator_cow_fork_refcount_roundtrip():
    """ISSUE 10: share -> write forks EXACTLY one page. fork() allocates
    one fresh refcount-1 page; the shared original keeps its refcount and
    cache entries for its other readers, and releasing the reader's ref
    returns it to cached-evictable, never the free list."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(4)
    (pid,) = alloc.alloc(1)
    alloc.register_partial(b"root", (7, 8, 9), pid)
    alloc.release(pid)                      # cached partial, refcount 0
    assert alloc.match_partial(b"root", (7, 8, 9, 1), cap=7) == (pid, 3)
    alloc.share(pid)                        # reader A maps it
    alloc.share(pid)                        # reader B maps it
    free_before = len(alloc.free)
    fork = alloc.fork(pid)
    assert fork is not None and fork != pid
    assert alloc.refcount[fork] == 1        # exactly one fresh page
    assert alloc.refcount[pid] == 2         # original untouched
    assert len(alloc.free) == free_before - 1
    alloc.release(pid)                      # A swapped to its fork
    alloc.release(pid)                      # B retired
    assert alloc.refcount.get(pid, 0) == 0
    assert pid not in alloc.free            # cached-evictable, not freed
    assert alloc.match_partial(b"root", (7, 8, 9), cap=7) == (pid, 3)


def test_page_allocator_shared_pin_survives_pressure():
    """Shared pages (full-block AND partial-tail) are pinned: allocation
    pressure may evict every refcount-0 cached page but never a pinned
    one."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(3)
    full, tail, spare = alloc.alloc(3)
    alloc.register_prefix(full, b"chain0", b"root")
    alloc.register_partial(b"chain0", (1, 2), tail)
    alloc.release(full)
    alloc.release(tail)
    alloc.release(spare)
    alloc.share(full)                       # pin both shared pages
    alloc.share(tail)
    assert alloc.available() == 1           # only the spare is claimable
    assert alloc.alloc(2) is None           # pins hold under pressure
    (got,) = alloc.alloc(1)
    assert got == spare
    assert alloc.lookup_prefix(b"chain0") == full
    assert alloc.match_partial(b"chain0", (1, 2, 3), cap=7) == (tail, 2)


def test_page_allocator_partial_match_boundaries():
    """Trie match on partial-block boundaries: the match is the longest
    common prefix of the cached tail and the request's remainder, capped
    by the caller; a diverging first row or a wrong parent yields none;
    the longest of several entries wins."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(4)
    a, b = alloc.alloc(2)
    alloc.register_partial(b"p", (5, 6, 7, 8), a)
    alloc.register_partial(b"p", (5, 6), b)
    # full 4-row entry matches but the cap clamps the usable rows
    assert alloc.match_partial(b"p", (5, 6, 7, 8, 9), cap=3) == (a, 3)
    # divergence mid-tail: only the common prefix is usable
    assert alloc.match_partial(b"p", (5, 6, 99), cap=7) == (a, 2)
    # first row diverges: no match at all
    assert alloc.match_partial(b"p", (4, 6, 7), cap=7) is None
    # parent scoping: same tokens under another chain never match
    assert alloc.match_partial(b"q", (5, 6, 7), cap=7) is None


def test_page_allocator_trie_eviction_unlinks_subtree():
    """Evicting an interior chain node makes its cached descendants
    unreachable: they are unlinked and returned to the free pool (leaf
    entries are preferred victims, so this only happens once every leaf
    is gone)."""
    from ray_tpu.llm.engine import PageAllocator

    alloc = PageAllocator(3)
    p0, p1, tail = alloc.alloc(3)
    alloc.register_prefix(p0, b"c0", b"root")
    alloc.register_prefix(p1, b"c1", b"c0")
    alloc.register_partial(b"c1", (3, 4), tail)
    for pid in (p0, p1, tail):
        alloc.release(pid)
    assert alloc.available() == 3
    # leaf-first: the partial tail (a leaf) goes before the chain nodes
    (first,) = alloc.alloc(1)
    assert first == tail
    # evicting c0 (interior: c1 still hangs under it) unlinks c1 too
    alloc.release(first)  # plain free page now
    got = alloc.alloc(3)
    assert got is not None and set(got) == {p0, p1, tail}
    assert alloc.lookup_prefix(b"c0") is None
    assert alloc.lookup_prefix(b"c1") is None
    assert alloc.match_partial(b"c1", (3, 4), cap=7) is None

    # CASCADE: an interior node evicted while its child is PINNED — the
    # child loses its (unreachable) cache entry but stays allocated to
    # its reader, and only frees on the reader's final release.
    alloc2 = PageAllocator(2)
    q0, q1 = alloc2.alloc(2)
    alloc2.register_prefix(q0, b"d0", b"root")
    alloc2.register_prefix(q1, b"d1", b"d0")
    alloc2.release(q0)        # cached, refcount 0 — the only victim
    alloc2.share(q1)
    alloc2.release(q1)        # refcount 1: pinned by its reader
    (got2,) = alloc2.alloc(1)
    assert got2 == q0
    assert alloc2.lookup_prefix(b"d1") is None   # unlinked with parent
    alloc2.release(q1)
    assert q1 in alloc2.free  # pinned child frees on final release


def test_engine_cached_vs_cold_greedy_parity(small_model):
    """ISSUE 10 acceptance: greedy decode is byte-identical between a
    prefix-cached engine (full-block hits + a partial-tail COW fork,
    including a mid-sequence divergence) and naive full recompute, on
    uniform and mixed-batch workloads."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8)
    prompt_a = list(range(1, 20))           # 19 tokens: 2 full pages + 3
    a = Request("a", list(prompt_a), max_new_tokens=4)
    eng.add_request(a)
    while not a.done:
        eng.step()
    assert a.generated == naive_greedy(params, cfg, prompt_a, 4)
    # Retire registered pages 0,1 as full blocks and the partial tail
    # (prompt rows 16-18 + generated rows) for COW sharing.

    # Uniform resend: full hits + partial rows -> only the last prompt
    # token is computed; the first suffix write forks the shared tail.
    b = Request("b", list(prompt_a), max_new_tokens=4)
    eng.add_request(b)
    while not b.done:
        eng.step()
    assert b.generated == a.generated
    assert b.cached_prefix_tokens == 18     # 2 pages + 2 partial rows
    assert eng.metrics["cow_forks"] >= 1

    # Mixed batch with a COW DIVERGENCE mid-sequence: two prompts share
    # the cached chain but diverge inside the partial tail block; both
    # map the shared page, each forks its own copy, and both decode
    # byte-identically to full recompute.
    forks_before = eng.metrics["cow_forks"]
    prompt_c = prompt_a[:17] + [99, 98, 97]
    prompt_d = prompt_a[:17] + [77, 76, 75, 74]
    c = Request("c", list(prompt_c), max_new_tokens=5)
    d = Request("d", list(prompt_d), max_new_tokens=5)
    eng.add_request(c)
    eng.add_request(d)
    while not (c.done and d.done):
        eng.step()
    assert c.generated == naive_greedy(params, cfg, prompt_c, 5)
    assert d.generated == naive_greedy(params, cfg, prompt_d, 5)
    assert c.cached_prefix_tokens == 17 and d.cached_prefix_tokens == 17
    assert eng.metrics["cow_forks"] >= forks_before + 2
    assert eng.metrics["prefix_cached_tokens"] > 0
    assert 0.0 < eng.prefill_suffix_frac < 1.0

    # COLD control: identical workload on a cache-disabled engine.
    cold = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                           enable_prefix_cache=False)
    for rid, p, n in (("a2", prompt_a, 4), ("b2", prompt_a, 4),
                      ("c2", prompt_c, 5), ("d2", prompt_d, 5)):
        r = Request(rid, list(p), max_new_tokens=n)
        cold.add_request(r)
        while not r.done:
            cold.step()
        hot = {"a2": a, "b2": b, "c2": c, "d2": d}[rid]
        assert r.generated == hot.generated, rid
    assert cold.metrics["prefix_cached_tokens"] == 0


def test_engine_multiturn_session_reuse(small_model):
    """Multi-turn session: turn 2's prompt embeds turn 1's prompt AND
    generated answer verbatim — generated-token pages registered at
    retire make the whole previous exchange a cache hit."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    turn1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]       # 11 tokens
    r1 = Request("t1", list(turn1), max_new_tokens=8)
    eng.add_request(r1)
    while not r1.done:
        eng.step()
    assert r1.generated == naive_greedy(params, cfg, turn1, 8)
    follow = turn1 + r1.generated + [8, 8, 8]        # turn-2 prompt
    r2 = Request("t2", list(follow), max_new_tokens=4)
    eng.add_request(r2)
    while not r2.done:
        eng.step()
    assert r2.generated == naive_greedy(params, cfg, follow, 4)
    # 11 + 8 = 19 tokens of context; everything the engine wrote K/V
    # for (up to the last generated token) is reusable.
    assert r2.cached_prefix_tokens >= 16             # ≥ the 2 full pages


def test_mixed_dispatch_bounds_inter_token_latency(small_model):
    """ISSUE 7 acceptance: with a 2k-ish prompt admitted mid-stream, the
    token-budget mixed schedule keeps every running stream's max
    inter-token step gap STRICTLY below the legacy prefill-first
    schedule's, with byte-identical generated tokens."""
    cfg, params = small_model

    def run(budget, starvation):
        eng = InferenceEngine(
            cfg, params, max_slots=4, max_len=128, page_size=8,
            prefill_chunk_size=16, decode_steps_per_dispatch=2,
            prefill_token_budget=budget,
            decode_starvation_limit=starvation)
        a = Request("a", [1, 2, 3], max_new_tokens=30)
        eng.add_request(a)
        step_idx = 0
        emits: dict[str, list[int]] = {}

        def tick():
            nonlocal step_idx
            step_idx += 1
            for e in eng.step():
                emits.setdefault(e["request_id"], []).append(step_idx)

        for _ in range(4):
            tick()  # `a` is streaming
        long_prompt = list(range(1, 100))  # 99 tokens -> 7 chunks of 16
        b = Request("b", long_prompt, max_new_tokens=4)
        eng.add_request(b)
        while not (a.done and b.done):
            tick()
            assert step_idx < 500
        gaps = [j - i for i, j in zip(emits["a"], emits["a"][1:])]
        return a.generated, b.generated, max(gaps), eng.metrics

    # budget 0 + guard off = the old strict prefill-first schedule
    gen_a_old, gen_b_old, gap_old, m_old = run(budget=0, starvation=0)
    gen_a_mix, gen_b_mix, gap_mix, m_mix = run(budget=None, starvation=8)
    assert gen_a_mix == gen_a_old       # byte-identical running stream
    assert gen_b_mix == gen_b_old       # byte-identical admitted prompt
    assert gap_mix < gap_old, (gap_mix, gap_old)
    assert m_mix["engine_step_mix"]["mixed"] > 0
    assert m_old["decode_stall_steps"] >= 7   # one per prefill chunk
    assert m_mix["decode_stall_steps"] == 0   # decode rode every dispatch
    # and both agree with the ground-truth forward
    assert gen_a_mix == naive_greedy(params, cfg, [1, 2, 3], 30)
    assert gen_b_mix == naive_greedy(params, cfg, list(range(1, 100)), 4)


def test_decode_starvation_guard_on_legacy_path(small_model):
    """With mixed dispatch disabled (budget 0) the starvation guard still
    bounds decode stalls: after `decode_starvation_limit` consecutive
    prefill-only steps a decode burst is forced."""
    cfg, params = small_model
    eng = InferenceEngine(
        cfg, params, max_slots=4, max_len=128, page_size=8,
        prefill_chunk_size=16, decode_steps_per_dispatch=2,
        prefill_token_budget=0, decode_starvation_limit=2)
    a = Request("a", [1, 2, 3], max_new_tokens=30)
    eng.add_request(a)
    step_idx = 0
    emits: list[int] = []

    def tick():
        nonlocal step_idx
        step_idx += 1
        for e in eng.step():
            if e["request_id"] == "a":
                emits.append(step_idx)

    for _ in range(4):
        tick()
    b = Request("b", list(range(1, 100)), max_new_tokens=4)
    eng.add_request(b)
    while not (a.done and b.done):
        tick()
        assert step_idx < 500
    gaps = [j - i for i, j in zip(emits, emits[1:])]
    # guard fires after 2 stalled steps: gap bounded by limit+1, far
    # below the 8-step head-of-line block of the unguarded schedule
    assert max(gaps) <= 3, gaps
    assert eng.metrics["engine_step_mix"]["mixed"] == 0
    assert a.generated == naive_greedy(params, cfg, [1, 2, 3], 30)
    assert b.generated == naive_greedy(params, cfg, list(range(1, 100)), 4)


def test_mixed_dispatch_multi_prompt_budget(small_model):
    """Several admitted prompts share one mixed dispatch up to
    max_prefill_seqs_per_step/prefill_token_budget, and the
    prefix-cache hit-rate metric tracks lookups vs hits."""
    cfg, params = small_model
    eng = InferenceEngine(
        cfg, params, max_slots=4, max_len=64, page_size=8,
        prefill_chunk_size=16, decode_steps_per_dispatch=2,
        prefill_token_budget=32, max_prefill_seqs_per_step=2)
    a = Request("a", [1, 2, 3], max_new_tokens=24)
    eng.add_request(a)
    for _ in range(3):
        eng.step()
    reqs = [Request(f"p{i}", [10 + i] * 20, max_new_tokens=3)
            for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    n = 0
    while not all(r.done for r in reqs + [a]):
        eng.step()
        n += 1
        assert n < 500
    assert eng.metrics["engine_step_mix"]["mixed"] > 0
    for r, orig in zip(reqs, range(3)):
        assert r.generated == naive_greedy(params, cfg, [10 + orig] * 20, 3)
    assert a.generated == naive_greedy(params, cfg, [1, 2, 3], 24)
    # hit-rate plumbing: lookups recorded, rate in [0, 1]
    assert eng.metrics["prefix_lookup_pages"] > 0
    assert 0.0 <= eng.prefix_cache_hit_rate <= 1.0
