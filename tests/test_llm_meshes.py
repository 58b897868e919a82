"""The engine on tp, pp and composed tp x pp meshes (8 virtual CPU devices):
token-identical to the single-device engine, LoRA stacks and COW prefix
pages included."""

import jax
import numpy as np
import pytest

from llm_cases import _make_adapter, naive_greedy, small_model  # noqa: F401
from ray_tpu.llm.engine import InferenceEngine, Request


def test_tensor_parallel_engine_parity(small_model):
    """The engine sharded over a tp mesh (params by heads/kv_heads, pages
    by kv_heads; XLA inserts the collectives) decodes token-identically
    to the single-device engine — the multi-chip inference path the
    reference gets from vLLM's TP workers."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompt = list(range(1, 22))
    ref = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8)
    expected = ref.generate(list(prompt), max_new_tokens=6)

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(tp=2, dp=max(1, n // 2)))
    tp_eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                             mesh=mesh)
    assert tp_eng.generate(list(prompt), max_new_tokens=6) == expected

    with pytest.raises(ValueError, match="not divisible"):
        InferenceEngine(cfg, params, mesh=create_mesh(MeshConfig(tp=8, dp=max(1, n // 8))),
                        max_slots=2, max_len=64, page_size=8)


def test_pipeline_parallel_engine_parity(small_model):
    """The engine staged over a pp mesh (layers AND the page pool sharded
    by stage, activations rotating via ppermute, decode pipelined over
    slot groups — llm/pp_model.py) decodes token-identically to the
    single-device engine. The reference gets PP from vLLM workers with
    NCCL send/recv (vllm_models.py:117-168)."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompts = [list(range(1, 22)), [7, 3, 7, 3, 7],
               [2, 4, 6, 8, 10, 12, 14, 16, 18]]
    ref = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8)
    expected = [ref.generate(list(p), max_new_tokens=6) for p in prompts]

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, dp=max(1, n // 2)))
    pp_eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                             mesh=mesh)
    got = [pp_eng.generate(list(p), max_new_tokens=6) for p in prompts]
    assert got == expected

    # oversubscribed: more concurrent requests than slots, mid-flight EOS
    many = [ref.generate([5, 9, 13], max_new_tokens=4) for _ in range(6)]
    got_many = [pp_eng.generate([5, 9, 13], max_new_tokens=4) for _ in range(6)]
    assert got_many == many

    with pytest.raises(ValueError, match="max_slots"):
        InferenceEngine(cfg, params, mesh=mesh, max_slots=3, max_len=64,
                        page_size=8)


def test_lora_pp_decode_parity(small_model, tmp_path):
    """LoRA over a PIPELINE mesh (round 8): the adapter stacks shard over
    pp on their layer axis like the params, prefill carries the adapter
    into the chunk's K/V (pp_prefill_chunk lora path), and a decode
    batch mixing base and adapter requests must produce byte-identical
    greedy tokens to the single-device multi-LoRA engine."""
    from ray_tpu.llm.lora import LoRAServingConfig, save_adapter
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    rng = np.random.default_rng(11)
    save_adapter(str(tmp_path / "adp.npz"), _make_adapter(cfg, rng))
    lora = LoRAServingConfig(max_loras=2, max_rank=4,
                             dynamic_lora_loading_path=str(tmp_path))
    prompts = [([3, 1, 4, 1, 5, 9, 2, 6], None),
               ([3, 1, 4, 1, 5, 9, 2, 6], "adp"),
               ([2, 7, 1, 8], "adp"),
               ([2, 7, 1, 8], None)]

    def run(mesh):
        eng = InferenceEngine(cfg, params, max_slots=4, max_len=64,
                              page_size=8, lora_config=lora, mesh=mesh)
        reqs = [Request(f"r{i}", list(p), max_new_tokens=6, model=m)
                for i, (p, m) in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while any(not r.done for r in reqs):
            eng.step()
        assert all(r.finish_reason != "admission_failed" for r in reqs)
        return [r.generated for r in reqs]

    expected = run(None)
    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, dp=max(1, n // 2)))
    assert run(mesh) == expected
    assert expected[0] != expected[1]  # the adapter actually does something


def test_tp_pp_composed_engine_parity(small_model):
    """TP x PP inference: layers staged over pp with tp auto-partitioned
    INSIDE each stage (partial-manual shard_map, axis_names={"pp"}) must
    stay token-identical to the single-device engine — the composed
    placement the reference gets from vLLM (vllm_models.py:117-168)."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompts = [list(range(1, 22)), [7, 3, 7, 3, 7],
               [2, 4, 6, 8, 10, 12, 14, 16, 18]]
    ref = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8)
    expected = [ref.generate(list(p), max_new_tokens=6) for p in prompts]

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, tp=2, dp=max(1, n // 4)))
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          mesh=mesh)
    got = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
    assert got == expected


def test_pp_chunk_pipelined_prefill_parity(small_model):
    """Long prompts prefill as a chunk WAVEFRONT through the pp stages
    (pp_model.pp_prefill_chunks): up to pp consecutive full-size chunks
    per dispatch, token-identical to the single-device engine."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    prompt = list(range(1, 41))                    # 40 tokens: 2 full + tail
    ref = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          prefill_chunk_size=16)
    expected = ref.generate(list(prompt), max_new_tokens=6)

    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, dp=max(1, n // 2)))
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          prefill_chunk_size=16, mesh=mesh)
    got = eng.generate(list(prompt), max_new_tokens=6)
    assert got == expected
    # the pipelined path actually ran: 40 tokens = 2 pipelined + 1 tail
    assert eng.metrics["prefill_chunks"] >= 3


def test_pp_partial_block_cow_parity(small_model):
    """Round 15 (PR 10 residue a): pp engines admit PARTIAL-block prefix
    hits. The pp prefill scatters rows at (page, offset) granularity, so
    a cached suffix can start mid-page on a COW-forked shared page —
    `supports_prefix_cow` is no longer gated off the pp path. Cached
    resend and a mid-tail divergence must decode byte-identically to
    full recompute, with real COW forks on the trie."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg, params = small_model
    n = len(jax.devices())
    mesh = create_mesh(MeshConfig(pp=2, dp=max(1, n // 2)))
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          mesh=mesh)
    assert eng._cow_enabled, "pp executor must support prefix COW now"

    prompt_a = list(range(1, 20))           # 2 full pages + 3 partial rows
    a = Request("a", list(prompt_a), max_new_tokens=4)
    eng.add_request(a)
    while not a.done:
        eng.step()
    assert a.generated == naive_greedy(params, cfg, prompt_a, 4)

    # Uniform resend: full-block hits + partial tail rows -> the suffix
    # starts MID-PAGE and the first write COW-forks the shared tail.
    b = Request("b", list(prompt_a), max_new_tokens=4)
    eng.add_request(b)
    while not b.done:
        eng.step()
    assert b.generated == a.generated
    assert b.cached_prefix_tokens == 18     # 2 pages + 2 partial rows
    assert eng.metrics["cow_forks"] >= 1

    # Mid-tail divergence: shares the chain, diverges inside the partial
    # block — forks its own copy, decodes identically to recompute.
    forks_before = eng.metrics["cow_forks"]
    prompt_c = prompt_a[:17] + [99, 98, 97]
    c = Request("c", list(prompt_c), max_new_tokens=5)
    eng.add_request(c)
    while not c.done:
        eng.step()
    assert c.generated == naive_greedy(params, cfg, prompt_c, 5)
    assert c.cached_prefix_tokens == 17
    assert eng.metrics["cow_forks"] > forks_before
