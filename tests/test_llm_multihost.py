"""Multi-host LLM engine: an engine that SPANS hosts via per-host shard
actors + jax.distributed (reference ``vllm_models.py:117-168`` places
TP×PP engines across nodes with placement groups; SURVEY §7.1 calls this
SPMD↔actor bridge *the* architectural delta).

Multi-host is simulated the way the reference's tests simulate multi-node:
each shard actor is a real worker process with ONE local CPU device
(``xla_force_host_platform_device_count=1``), joined into one global
2-device mesh by ``jax.distributed.initialize`` with gloo cross-process
collectives — the same code path a v5e pod takes over ICI.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from llm_cases import within
from ray_tpu.llm import InferenceEngine, create_sharded_executor
from ray_tpu.llm.serving import LLMDeployment
from ray_tpu.models.llama import PRESETS

# Each shard process sees exactly one local CPU device; two shards form
# the 2-device global mesh.
SHARD_ENV = {"env_vars": {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}}


@pytest.fixture(scope="module")
def small_cfg():
    return dataclasses.replace(
        PRESETS["debug"], dtype=jnp.float32, attn_impl="reference")


def test_multihost_engine_token_parity(ray_cluster, small_cfg):
    """2 shard processes × 1 device each == one 2-device tp mesh: decoded
    tokens must match the single-process engine exactly (greedy)."""
    prompts = [list(range(1, 22)), [7, 3, 7, 3, 7], [2, 4, 6, 8, 10, 12, 14, 16, 18]]

    ref = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8, seed=0)
    expected = [ref.generate(list(p), max_new_tokens=6) for p in prompts]

    executor = within(
        60, create_sharded_executor,
        small_cfg, 2,
        max_slots=2,
        num_pages=InferenceEngine.total_pages(2, 64, 8),
        page_size=8,
        seed=0,
        runtime_env=SHARD_ENV,
    )
    try:
        eng = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8,
                              executor=executor, seed=0)
        got = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
        assert got == expected
        # the wait for the shards is the step's sync, as the device's on one host
        assert 0 < eng.metrics["step_sync_ms_sum"] <= executor.sync_s * 1e3
        assert eng.metrics["step_host_ms_sum"] > 0
    finally:
        executor.shutdown()


def test_multihost_compiled_loop_token_parity(ray_cluster, small_cfg):
    """The compiled-loop tick path (round 8): the SAME shard fleet driven
    through a persistent dag/loop.py pipeline — one owner-side submit per
    shard, then every engine operation is a channel write/read with zero
    per-tick RPC — must decode byte-identically to the per-call dynamic
    path (channel FIFO ordering preserves the SPMD invariant exactly as
    per-caller actor ordering did)."""
    prompts = [list(range(1, 22)), [7, 3, 7, 3, 7]]

    ref = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8, seed=0)
    expected = [ref.generate(list(p), max_new_tokens=6) for p in prompts]

    executor = within(
        60, create_sharded_executor,
        small_cfg, 2,
        max_slots=2,
        num_pages=InferenceEngine.total_pages(2, 64, 8),
        page_size=8,
        seed=0,
        runtime_env=SHARD_ENV,
        use_compiled_loop=True,
    )
    try:
        assert executor.use_compiled_loop and executor._loop is not None
        eng = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8,
                              executor=executor, seed=0)
        got = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
        assert got == expected
        # every prefill/sample/decode streamed through the loop, and the
        # engine surfaces the count
        assert executor.loop_ticks > 0
        assert eng.metrics["dag_loop_ticks"] == executor.loop_ticks
        assert 0 < eng.metrics["step_sync_ms_sum"] <= executor.sync_s * 1e3
    finally:
        executor.shutdown()


def test_multihost_pp_token_parity(ray_cluster, small_cfg):
    """Pipeline parallelism across hosts: 2 shard processes × 1 device
    each form a pp=2 mesh — each host holds HALF the layers and half the
    page pool, activations cross hosts via ppermute (llm/pp_model.py).
    Tokens must match the single-process engine exactly (greedy)."""
    prompts = [list(range(1, 22)), [7, 3, 7, 3, 7]]

    ref = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8, seed=0)
    expected = [ref.generate(list(p), max_new_tokens=6) for p in prompts]

    executor = within(
        60, create_sharded_executor,
        small_cfg, 2,
        max_slots=2,
        num_pages=InferenceEngine.total_pages(2, 64, 8),
        page_size=8,
        pp=2,
        seed=0,
        runtime_env=SHARD_ENV,
    )
    try:
        eng = InferenceEngine(small_cfg, max_slots=2, max_len=64, page_size=8,
                              executor=executor, seed=0)
        got = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
        assert got == expected
    finally:
        executor.shutdown()


def test_multihost_deployment_generates(ray_cluster):
    """The Serve deployment path: ``num_hosts=2`` builds the shard fleet
    behind one replica-facing engine; requests flow scheduler -> shards."""
    cfg = dataclasses.replace(
        PRESETS["debug-128"], dtype=jnp.float32, attn_impl="reference")
    dep = within(
        60, LLMDeployment,
        cfg, max_slots=2, max_len=64, page_size=8,
        prefill_chunk_size=16, decode_steps_per_dispatch=4,
        num_hosts=2, shard_resources={"CPU": 0.5},
        shard_runtime_env=SHARD_ENV, request_timeout_s=60,
    )
    try:
        out = dep.generate("ab", max_new_tokens=4)
        assert out["num_generated"] == 4
        assert out["finish_reason"] in ("length", "stop")
    finally:
        dep.close()
