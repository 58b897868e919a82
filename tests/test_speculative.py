"""Speculative decoding: draft-K / verify-in-one-dispatch (ROADMAP 5).

The acceptance bar is LOSSLESSNESS: greedy speculative output must be
byte-identical to plain decode in every batch shape — uniform, skewed,
mixed draft quality, COW-shared prefixes, rejections landing mid-page,
EOS inside an accepted run — and a fully rejected draft still advances
one token per verify (speculation never yields less per forward than a
plain decode step). The multihost case drives the SAME verify fan-out
through the compiled-loop channel path.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_cases import within

from ray_tpu.llm.engine import InferenceEngine, Request
from ray_tpu.llm.speculative import Drafter, NgramDrafter, SpeculationConfig
from ray_tpu.models.llama import PRESETS, init_params


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# Prompts ending mid-pattern so the n-gram lookup drafts from step one.
REPETITIVE = [7, 2, 9, 7, 2, 9, 7, 2, 9, 7]


class WrongDrafter(Drafter):
    """Always proposes SOMETHING (so verify runs every decode tick);
    with ``impossible=True`` the proposals are out-of-vocab, which the
    greedy accept (argmax equality) can never match — a guaranteed
    accept-length-0 round every time."""

    def __init__(self, k: int = 3, impossible: bool = False, vocab: int = 0):
        self.k = k
        self.base = vocab if impossible else 0

    def draft(self, tokens, k):
        if self.base:
            return [self.base + i for i in range(min(k, self.k))]
        return [(tokens[-1] + 97 + i) % 199 + 1 for i in range(min(k, self.k))]


class OracleDrafter(Drafter):
    """Drafts the model's TRUE continuation (recorded from a plain run)
    — the deterministic high-accept case that drives accepted runs
    across page boundaries, shared prefixes, and EOS positions."""

    def __init__(self, seqs):
        self.seqs = [list(s) for s in seqs]

    def draft(self, tokens, k):
        n = len(tokens)
        for s in self.seqs:
            if len(s) > n and s[:n] == list(tokens):
                return s[n:n + k]
        return []


def _generate(cfg, params, prompts, *, speculation=None, max_new=10,
              eos_id=None, temps=None, max_slots=None, max_len=64,
              page_size=8, attention_impl="dense", executor=None,
              engine_out=False, **kw):
    eng = InferenceEngine(
        cfg, params if executor is None else None,
        max_slots=max_slots or max(2, len(prompts)), max_len=max_len,
        page_size=page_size, attention_impl=attention_impl,
        speculation_config=speculation, executor=executor, seed=0, **kw)
    mn = max_new if isinstance(max_new, list) else [max_new] * len(prompts)
    ts = temps or [0.0] * len(prompts)
    reqs = [Request(f"r{i}", list(p), mn[i], ts[i], eos_id=eos_id)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    steps = 0
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
        assert steps < 2000
    out = [list(r.generated) for r in reqs]
    return (out, eng) if engine_out else out


# --------------------------------------------------------------- drafter
def test_ngram_drafter_lookup():
    d = NgramDrafter(ngram_max=3, ngram_min=1)
    # trailing 3-gram [2,9,7]: MOST RECENT earlier occurrence is at
    # 4..6, whose continuation [2,9,7] runs to the end of the sequence
    assert d.draft(REPETITIVE, 3) == [2, 9, 7]
    assert d.draft(REPETITIVE, 8) == [2, 9, 7]  # capped by the seq end
    assert d.draft([1, 2, 3, 4, 5], 4) == []             # no repetition
    assert d.draft([5], 4) == []                         # too short
    assert d.draft(REPETITIVE, 0) == []
    # most RECENT earlier occurrence wins
    assert d.draft([1, 9, 2, 8, 9, 3, 9], 2) == [3, 9]


def test_speculation_config_normalize():
    assert SpeculationConfig.normalize(None) is None
    c = SpeculationConfig.normalize({"num_draft_tokens": 6})
    assert c.num_draft_tokens == 6
    assert isinstance(c.build_drafter(), NgramDrafter)
    assert SpeculationConfig.normalize(c) is c
    wrong = WrongDrafter()
    assert SpeculationConfig(drafter=wrong).build_drafter() is wrong
    with pytest.raises(TypeError):
        SpeculationConfig.normalize("ngram")


# ---------------------------------------------------------------- parity
def test_greedy_parity_uniform(small_model):
    cfg, params = small_model
    prompts = [list(REPETITIVE) for _ in range(4)]
    plain = _generate(cfg, params, prompts)
    # the n-gram drafter finds nothing to draft in what THIS model
    # continues the prompt with, so the drafts are the model's own tokens
    oracle = OracleDrafter([list(p) + o for p, o in zip(prompts, plain)])
    spec, eng = _generate(
        cfg, params, prompts, engine_out=True,
        speculation=SpeculationConfig(num_draft_tokens=4, drafter=oracle))
    assert spec == plain
    assert eng.metrics["spec_dispatches"] > 0  # speculation actually ran
    assert eng.metrics["spec_drafted_tokens"] > 0


def test_greedy_parity_skewed_mixed_batch(small_model):
    """Mixed draft quality and skewed lengths in ONE batch: repetitive
    prompts draft well, arbitrary ones draft badly or not at all, and
    per-slot accept lengths diverge inside each verify dispatch."""
    cfg, params = small_model
    prompts = [list(REPETITIVE), [3, 1, 4, 1, 5, 9, 2, 6], [11] * 14,
               [2, 7]]
    max_new = [12, 6, 9, 4]
    plain = _generate(cfg, params, prompts, max_new=max_new)
    spec = _generate(cfg, params, prompts, max_new=max_new,
                     speculation={"num_draft_tokens": 5})
    assert spec == plain


def test_greedy_parity_cow_shared_prefix(small_model):
    """Speculation over COW-shared prefix pages: warm the prefix trie
    (retiring full blocks AND a partial tail), then decode a batch
    whose prompts map shared pages — the partial-tail hit COW-forks at
    the first suffix write, and accepted speculative runs write past
    the fork. Byte parity with plain decode, and the shared pages stay
    byte-stable (same trie hit/fork counts in both runs)."""
    cfg, params = small_model
    # 19 prompt + 4 generated -> 22 valid rows: 2 full pages + a
    # 6-row partial tail enters the trie at warm-request retire.
    warm = list(range(1, 20))
    batch = [warm[:17] + [31, 32], warm[:12] + [41, 42, 43], list(warm)]

    def run(spec):
        eng = InferenceEngine(cfg, params, max_slots=4, max_len=64,
                              page_size=8, speculation_config=spec, seed=0)
        first = eng.generate(list(warm), max_new_tokens=4)
        reqs = [Request(f"c{i}", list(p), 8) for i, p in enumerate(batch)]
        for r in reqs:
            eng.add_request(r)
        while any(not r.done for r in reqs):
            eng.step()
        hits = eng.metrics["prefix_hit_pages"]
        forks = eng.metrics["cow_forks"]
        return first, [list(r.generated) for r in reqs], hits, forks, eng

    p_first, p_out, p_hits, p_forks, _ = run(None)
    oracle = OracleDrafter([list(warm) + p_first]
                           + [list(p) + o for p, o in zip(batch, p_out)])
    s_first, s_out, s_hits, s_forks, eng = run(
        SpeculationConfig(num_draft_tokens=4, drafter=oracle))
    assert (s_first, s_out) == (p_first, p_out)
    assert s_hits == p_hits and s_hits > 0      # shared pages really mapped
    assert s_forks == p_forks and s_forks > 0   # and the COW fork fired
    assert eng.metrics["spec_accepted_tokens"] > 0


def test_greedy_parity_mid_page_rejection(small_model):
    """Rejections landing mid-page: a wrong-by-construction drafter is
    rejected at EVERY position offset as decode sweeps page
    boundaries; the trash-redirected commits must never corrupt the
    slot's real pages (parity over a full multi-page generation)."""
    cfg, params = small_model
    prompts = [[5, 9, 2], [6, 6, 6, 6, 6]]
    plain = _generate(cfg, params, prompts, max_new=21)
    spec, eng = _generate(
        cfg, params, prompts, max_new=21,
        speculation=SpeculationConfig(num_draft_tokens=3,
                                      drafter=WrongDrafter()),
        engine_out=True)
    assert spec == plain
    assert eng.metrics["spec_rollbacks"] > 0


def test_greedy_parity_eos_inside_accepted_run(small_model):
    """EOS emitted INSIDE an accepted draft run (the oracle drafts the
    true continuation, so the EOS position is mid-run) ends the stream
    exactly where plain decode ends it, discarding the verified
    surplus."""
    cfg, params = small_model
    prompt = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]  # varied greedy continuation
    probe = _generate(cfg, params, [list(prompt)], max_new=12)[0]
    # EOS = a token first emitted at position >= 3: the oracle's draft
    # reaches it only after accepted tokens, so EOS lands mid-run.
    idx = next(p for p in range(3, len(probe))
               if probe[p] not in probe[:p] and probe[p] not in prompt)
    eos = probe[idx]
    plain = _generate(cfg, params, [list(prompt)], max_new=12, eos_id=eos)
    assert len(plain[0]) == idx + 1
    oracle = OracleDrafter([list(prompt) + probe])
    spec, eng = _generate(
        cfg, params, [list(prompt)], max_new=12, eos_id=eos,
        speculation=SpeculationConfig(num_draft_tokens=6, drafter=oracle),
        engine_out=True)
    assert spec == plain
    assert spec[0][-1] == eos and len(spec[0]) == len(plain[0])
    assert eng.metrics["spec_dispatches"] > 0
    assert eng.metrics["spec_accepted_tokens"] > 0


def test_accept_zero_still_advances(small_model):
    """The progress floor: a draft rejected wholesale still emits one
    (corrected) token per slot per verify — tokens-per-dispatch can
    never drop below 1.0, so speculation never does worse per forward
    than plain decode."""
    cfg, params = small_model
    spec, eng = _generate(
        cfg, params, [[3, 1, 4, 1, 5], [2, 7, 1, 8]], max_new=9,
        speculation=SpeculationConfig(
            num_draft_tokens=4,
            drafter=WrongDrafter(impossible=True, vocab=cfg.vocab_size)),
        engine_out=True)
    plain = _generate(cfg, params, [[3, 1, 4, 1, 5], [2, 7, 1, 8]],
                      max_new=9)
    assert spec == plain
    assert eng.metrics["spec_dispatches"] > 0
    assert eng.metrics["spec_accepted_tokens"] == 0
    assert eng.spec_tokens_per_dispatch == 1.0


def test_tokens_per_dispatch_beats_plain_on_repetitive(small_model):
    """The sandbox acceptance cell: on repetitive traffic the n-gram
    drafter gets real accepts, so emitted tokens per slot per verify
    strictly beat the 1-token-per-forward plain baseline."""
    cfg, params = small_model
    prompts = [[5 + i, 9, 2, 5 + i, 9, 2, 5 + i, 9, 2, 5 + i]
               for i in range(4)]
    out, eng = _generate(cfg, params, prompts, max_new=60, max_len=128,
                         page_size=8,
                         speculation={"num_draft_tokens": 6},
                         engine_out=True)
    assert eng.spec_tokens_per_dispatch > 1.0
    assert eng.spec_accept_rate > 0.0
    assert 0.0 <= eng.spec_accept_rate <= 1.0
    plain = _generate(cfg, params, prompts, max_new=60, max_len=128,
                      page_size=8)
    assert out == plain


def test_paged_kernel_verify_parity(small_model):
    """The verify program's paged path (Pallas kernel folding staged
    rows [0, j] per chunk position, interpret mode here) matches the
    dense plain-decode ground truth byte for byte."""
    cfg, params = small_model
    prompts = [list(REPETITIVE), [4, 8, 4, 8, 4]]
    plain = _generate(cfg, params, prompts, max_new=8)
    oracle = OracleDrafter([list(p) + o for p, o in zip(prompts, plain)])
    spec, eng = _generate(
        cfg, params, prompts, max_new=8, attention_impl="paged",
        speculation=SpeculationConfig(num_draft_tokens=3, drafter=oracle),
        engine_out=True)
    assert spec == plain
    assert eng.metrics["spec_dispatches"] > 0
    assert eng.metrics["spec_accepted_tokens"] > 0


def test_temperature_rejection_sampling_sane(small_model):
    """temp > 0 runs the rejection-sampling path: requests complete
    with valid token ids (never a -1 pad) and full lengths. (Exact
    byte parity is a greedy-only guarantee — sampled runs consume RNG
    differently but preserve the target distribution.)"""
    cfg, params = small_model
    out, eng = _generate(
        cfg, params, [list(REPETITIVE), [1, 3, 1, 3, 1]],
        max_new=10, temps=[0.8, 0.6],
        speculation=SpeculationConfig(num_draft_tokens=3,
                                      drafter=WrongDrafter()),
        engine_out=True)
    assert all(len(t) == 10 for t in out)
    assert all(0 <= tok < cfg.vocab_size for t in out for tok in t)
    assert eng.metrics["spec_dispatches"] > 0


def test_plain_path_untouched_without_config(small_model):
    """speculation_config=None must leave the decode path bit-for-bit
    alone: no drafter, no verify dispatches, spec metrics zero."""
    cfg, params = small_model
    out, eng = _generate(cfg, params, [list(REPETITIVE)], engine_out=True)
    assert not eng.speculation_enabled and eng._drafter is None
    assert eng.metrics["spec_dispatches"] == 0
    assert eng.metrics["spec_drafted_tokens"] == 0
    assert eng.spec_tokens_per_dispatch == 0.0
    assert out == _generate(cfg, params, [list(REPETITIVE)])


def test_speculation_gated_off_unsupported_executor(small_model):
    """An executor without the verify entry point (here: faked) keeps
    the engine on plain decode even with a config set."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=2, max_len=64, page_size=8,
                          speculation_config={"num_draft_tokens": 4})
    assert eng.speculation_enabled
    eng.executor.__dict__["_verify"] = None  # simulate a pp-style executor
    assert not eng.executor.supports_speculation
    assert not eng.speculation_enabled
    assert eng.generate(list(REPETITIVE), max_new_tokens=6)  # plain path


def test_deployment_threads_speculation_config(small_model):
    """speculation_config rides LLMDeployment → engine, and the engine
    metrics surface accept rate / tokens-per-dispatch for the probe."""
    from ray_tpu.llm.serving import LLMDeployment

    cfg, _ = small_model
    cfg128 = dataclasses.replace(PRESETS["debug-128"], dtype=jnp.float32,
                                 attn_impl="reference")
    dep = LLMDeployment(cfg128, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk_size=16, request_timeout_s=60,
                        speculation_config={"num_draft_tokens": 3})
    try:
        assert dep.engine.speculation_enabled
        out = dep.generate("abcabcabc", max_new_tokens=6)
        assert out["num_generated"] == 6
        m = dep.engine_metrics()
        assert m["speculation_enabled"] is True
        assert "spec_accept_rate" in m and "spec_tokens_per_dispatch" in m
    finally:
        dep.close()


def test_concurrent_adds_during_speculation(small_model):
    """Late arrivals join mid-speculation: prefill interleaves with
    verify ticks and every request's greedy output still matches its
    own single-request plain reference (greedy is batch-independent)."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, max_slots=4, max_len=64, page_size=8,
                          speculation_config={"num_draft_tokens": 4})
    first = Request("a", list(REPETITIVE), 16)
    eng.add_request(first)
    for _ in range(3):
        eng.step()
    late = Request("b", [4, 8, 4, 8, 4], 8)
    eng.add_request(late)
    steps = 0
    while not (first.done and late.done):
        eng.step()
        steps += 1
        assert steps < 500
    assert first.generated == _generate(cfg, params, [list(REPETITIVE)],
                                        max_new=16)[0]
    assert late.generated == _generate(cfg, params, [[4, 8, 4, 8, 4]],
                                       max_new=8)[0]


# ----------------------------------------------- multihost / compiled loop
def test_multihost_compiled_loop_speculative_parity(ray_cluster):
    """The verify fan-out through BOTH sharded dispatch modes — dynamic
    actor calls and the compiled-loop channel (one resident tick
    executor per shard, verify rides ``tick(("verify", ...))``) — must
    match the single-process plain engine byte for byte."""
    from ray_tpu.llm import create_sharded_executor

    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32,
                              attn_impl="reference")
    prompts = [list(REPETITIVE), [7, 3, 7, 3, 7]]
    ref = InferenceEngine(cfg, max_slots=2, max_len=64, page_size=8, seed=0)
    expected = [ref.generate(list(p), max_new_tokens=8) for p in prompts]
    # The drafter is DRIVER-side state (the shards only see verify
    # dispatches), so the oracle works unchanged across the fan-out —
    # and guarantees accepted runs stream through the channel path.
    oracle = OracleDrafter([list(p) + o for p, o in zip(prompts, expected)])

    shard_env = {"env_vars": {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}}
    for use_loop in (False, True):
        executor = within(
            60, create_sharded_executor,
            cfg, 2, max_slots=2,
            num_pages=InferenceEngine.total_pages(2, 64, 8), page_size=8,
            seed=0, runtime_env=shard_env, use_compiled_loop=use_loop)
        try:
            assert executor.supports_speculation
            eng = InferenceEngine(
                cfg, max_slots=2, max_len=64, page_size=8,
                executor=executor, seed=0,
                speculation_config=SpeculationConfig(num_draft_tokens=3,
                                                     drafter=oracle))
            assert eng.speculation_enabled
            got = [eng.generate(list(p), max_new_tokens=8) for p in prompts]
            assert got == expected, f"use_compiled_loop={use_loop}"
            assert eng.metrics["spec_dispatches"] > 0
            assert eng.metrics["spec_accepted_tokens"] > 0
            if use_loop:
                assert executor.loop_ticks > 0
        finally:
            executor.shutdown()
