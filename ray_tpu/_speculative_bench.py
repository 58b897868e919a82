"""Speculative-decoding bench: plain vs draft-K/verify decode tok/s.

ISSUE 13 acceptance cells, runnable standalone (``python -m ray_tpu.cli
bench speculative``) or inside ``bench.py``:

  * ``decode_tok_s_plain`` / ``decode_tok_s_speculative`` — steady-state
    engine decode throughput of the same repetitive-traffic batch
    through the plain fused-loop path and the draft-K/verify path. The
    on-chip acceptance bound (speculative ≥ 1.5× plain — decode there
    is weight-bandwidth-bound, so K+1 positions cost ~one forward) is
    owed with the next chip BENCH (ROADMAP 1b); this CPU sandbox is
    compute-bound per token, so only the cells + the ratio are recorded.
  * ``spec_accept_rate`` — drafted tokens the target accepted (0-1).
  * ``spec_tokens_per_dispatch`` — tokens emitted per slot per verify
    forward; the sandbox acceptance bar is strictly > 1.0 with the
    n-gram drafter on this repetitive traffic (accept-0 floors it at
    1.0, so speculation never pays more forwards per token than plain).
  * ``spec_parity`` — 1.0 iff the speculative greedy bytes match plain.

Set ``RAY_TPU_BENCH_SKIP_SPECULATIVE=1`` to leave ``*_skipped`` markers
that ``bench_check`` honors.
"""

from __future__ import annotations

import os
import time

SKIP_MARKERS = {
    "decode_tok_s_plain_skipped": True,
    "decode_tok_s_speculative_skipped": True,
    "spec_accept_rate_skipped": True,
    "spec_tokens_per_dispatch_skipped": True,
    "spec_parity_skipped": True,
}


def _prompts(n: int, length: int) -> list[list[int]]:
    """Repetitive prompts (distinct per slot): the traffic shape the
    n-gram self-drafter exists for — multi-turn resends, retrieval
    quotes, structured output."""
    out = []
    for i in range(n):
        period = [11 + i, 23, 37, 41 + i, 5, 17]
        out.append([period[j % len(period)] % 200 + 1
                    for j in range(length)])
    return out


def _bench_model(preset: str):
    """Config + params for the bench engines. Off-chip the dense path is
    the decode ground truth and must run f32: greedy parity between the
    chunk-shaped verify softmax and the pool-gather decode softmax is
    exact at f32, while bf16 can flip argmax near-ties on ulp-level
    reassociation. On chip the engines resolve to the paged kernel,
    whose verify/decode layouts are IDENTICAL — bf16 parity holds there
    by construction (tests/test_speculative.py covers both)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import PRESETS, init_params
    from ray_tpu.tpu import on_tpu

    cfg = PRESETS[preset]
    if not on_tpu():
        if os.environ.get("RAY_TPU_BENCH_ALLOW_CPU") != "1":
            raise RuntimeError(
                f"speculative bench found no chip ({jax.devices()}); "
                "RAY_TPU_BENCH_ALLOW_CPU=1 runs its f32 CPU smoke mode")
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  attn_impl="reference")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _run_decode(cfg, params, speculation, prompts, max_new: int,
                max_len: int, page_size: int):
    """One timed generation of the batch; returns (tok_s, outputs,
    engine)."""
    from ray_tpu.llm.engine import InferenceEngine, Request

    eng = InferenceEngine(
        cfg, params, max_slots=len(prompts), max_len=max_len,
        page_size=page_size, prefill_chunk_size=4 * page_size,
        speculation_config=speculation, seed=0)
    reqs = [Request(f"sb-{i}", list(p), max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    t0 = time.perf_counter()
    while any(not r.done for r in reqs):
        eng.step()
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in reqs)
    return total / dt, [list(r.generated) for r in reqs], eng


def run_speculative_bench(slots: int | None = None,
                          max_new: int | None = None,
                          draft_k: int | None = None) -> dict:
    if os.environ.get("RAY_TPU_BENCH_SKIP_SPECULATIVE") == "1":
        return dict(SKIP_MARKERS)
    preset = os.environ.get("RAY_TPU_SPEC_BENCH_PRESET", "debug-128")
    slots = slots or int(os.environ.get("RAY_TPU_SPEC_BENCH_SLOTS", "8"))
    max_new = max_new or int(os.environ.get("RAY_TPU_SPEC_BENCH_NEW", "96"))
    draft_k = draft_k or int(os.environ.get("RAY_TPU_SPEC_BENCH_K", "6"))
    page_size = 16
    prompt_len = int(os.environ.get("RAY_TPU_SPEC_BENCH_PROMPT", "48"))
    max_len = -(-(prompt_len + max_new + page_size) // page_size) * page_size
    prompts = _prompts(slots, prompt_len)
    spec_cfg = {"num_draft_tokens": draft_k}
    cfg, params = _bench_model(preset)

    # Warmup pair: compiles the prefill buckets, the fused decode loop,
    # AND the verify program off-measurement (steady-state serving never
    # sees first-touch XLA compiles).
    _run_decode(cfg, params, None, prompts, 8, max_len, page_size)
    _run_decode(cfg, params, spec_cfg, prompts, 8, max_len, page_size)

    plain_tok_s, plain_out, _ = _run_decode(
        cfg, params, None, prompts, max_new, max_len, page_size)
    spec_tok_s, spec_out, eng = _run_decode(
        cfg, params, spec_cfg, prompts, max_new, max_len, page_size)
    return {
        "decode_tok_s_plain": round(plain_tok_s, 1),
        "decode_tok_s_speculative": round(spec_tok_s, 1),
        "spec_accept_rate": round(eng.spec_accept_rate, 4),
        "spec_tokens_per_dispatch": round(eng.spec_tokens_per_dispatch, 3),
        "spec_parity": 1.0 if spec_out == plain_out else 0.0,
        "spec_drafted_tokens": eng.metrics["spec_drafted_tokens"],
        "spec_dispatches": eng.metrics["spec_dispatches"],
        "spec_draft_k_cfg": draft_k,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run_speculative_bench()))
