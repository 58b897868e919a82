"""The LLM engine behind Serve: OpenAI-compatible routes with SSE token
streaming over a cluster, the batch processor, the adapter a request's
``model`` names, and a request the engine refuses at admission (SURVEY
§7.2-7). The engine itself: ``tests/test_llm_engine.py``; tp and pp meshes:
``tests/test_llm_meshes.py``."""

import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from llm_cases import _make_adapter
from ray_tpu.models.llama import PRESETS


def test_openai_completions_http(ray_cluster):
    """OpenAI-compatible /v1/completions + /v1/chat/completions + /v1/models
    through the real proxy (reference routers/router.py:173)."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    try:
        serve.run(build_llm_app("debug-128", max_slots=4, max_len=128), name="llm")
        addr = serve.http_address()

        models = json.loads(urllib.request.urlopen(addr + "/v1/models", timeout=60).read())
        assert models["data"][0]["id"] == "debug-128"

        body = json.dumps({"prompt": "hello", "max_tokens": 8}).encode()
        req = urllib.request.Request(addr + "/v1/completions", data=body,
                                     headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] == 8
        assert out["choices"][0]["finish_reason"] == "length"

        body = json.dumps({"messages": [{"role": "user", "content": "hi"}],
                           "max_tokens": 4}).encode()
        req = urllib.request.Request(addr + "/v1/chat/completions", data=body,
                                     headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"]["role"] == "assistant"
    finally:
        serve.shutdown()


def test_openai_sse_streaming(ray_cluster):
    """stream=true responses arrive as SSE chunks (one per token, [DONE]
    terminated) through the proxy's chunked-transfer path."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    try:
        serve.run(build_llm_app("debug-128", max_slots=4, max_len=128), name="llm")
        addr = serve.http_address()
        body = json.dumps({"prompt": "hello", "max_tokens": 6, "stream": True}).encode()
        req = urllib.request.Request(addr + "/v1/completions", data=body,
                                     headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=60)
        assert resp.headers.get("Content-Type") == "text/event-stream"
        events = []
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(line[len("data: "):])
        assert events[-1] == "[DONE]"
        tokens = [json.loads(e)["choices"][0]["text"] for e in events[:-1]]
        assert len(tokens) == 6

        # chat streaming: role delta first, then content deltas
        body = json.dumps({"messages": [{"role": "user", "content": "hi"}],
                           "max_tokens": 3, "stream": True}).encode()
        req = urllib.request.Request(addr + "/v1/chat/completions", data=body,
                                     headers={"Content-Type": "application/json"})
        chunks = [l.decode().strip()[len("data: "):] for l in urllib.request.urlopen(req, timeout=60)
                  if l.decode().strip().startswith("data: ")]
        assert chunks[-1] == "[DONE]"
        assert json.loads(chunks[0])["choices"][0]["delta"] == {"role": "assistant"}
    finally:
        serve.shutdown()


def test_serve_llm_app_concurrent_http(ray_cluster):
    """An LLM app serves concurrent HTTP completions through the proxy
    (llm_server.py:415 acceptance surface)."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    try:
        app = build_llm_app("debug-128", max_slots=4, max_len=128)
        serve.run(app, name="llm")
        addr = serve.http_address()

        results: list[dict] = []
        errors: list[Exception] = []

        def one(i):
            q = urllib.parse.urlencode({"prompt": f"hello {i}", "max_new_tokens": 5})
            try:
                with urllib.request.urlopen(f"{addr}/?{q}", timeout=60) as resp:
                    results.append(json.loads(resp.read()))
            except Exception as e:  # pragma: no cover - surfaced by assert
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(results) == 6
        for r in results:
            assert r["num_generated"] == 5
            assert r["finish_reason"] in ("length", "stop")
    finally:
        serve.shutdown()


def test_batch_llm_processor(ray_cluster):
    """Data batch inference through the Processor pipeline (reference
    llm/_internal/batch/processor/base.py): rows in -> generated_text out,
    with per-row sampling columns and pre/postprocess stages."""
    from ray_tpu import data as rd
    from ray_tpu.llm import LLMProcessorConfig, build_llm_processor

    config = LLMProcessorConfig(preset="debug-128", concurrency=1, batch_size=8,
                                max_slots=4, max_len=128, max_tokens=6)
    processor = build_llm_processor(
        config,
        preprocess=lambda row: {"prompt": f"say {row['word']}",
                                "max_tokens": 4 + (row["id"] % 3),
                                "word": row["word"], "id": row["id"]},
        postprocess=lambda row: {"word": row["word"],
                                 "text": row["generated_text"],
                                 "n": row["num_generated_tokens"]},
    )
    rows = [{"id": i, "word": w} for i, w in enumerate(["alpha", "beta", "gamma",
                                                        "delta", "epsilon", "zeta"])]
    out = processor(rd.from_items(rows, parallelism=2)).take_all()
    assert len(out) == 6
    by_word = {r["word"]: r for r in out}
    assert set(by_word) == {w["word"] for w in rows}
    for i, w in enumerate(["alpha", "beta", "gamma", "delta", "epsilon", "zeta"]):
        assert by_word[w]["n"] == 4 + (i % 3)  # per-row max_tokens honored
        assert isinstance(by_word[w]["text"], str)


def test_lora_openai_route(tmp_path):
    """`model` field on /v1/completions selects the adapter (reference
    LLMRouter + multiplex routing), no cluster needed: the tuned request
    returns ``max_tokens`` completion tokens under the adapter's name,
    and they are not the base model's."""
    from ray_tpu.llm.lora import save_adapter
    from ray_tpu.llm.serving import LLMDeployment

    rng = np.random.default_rng(3)
    # the adapter has the widths of the config that is SERVED
    save_adapter(str(tmp_path / "tone.npz"),
                 _make_adapter(PRESETS["debug-128"], rng, scale=4.0))
    dep = LLMDeployment(
        "debug-128", max_slots=2, max_len=64, request_timeout_s=30,
        lora_config={"max_loras": 2, "max_rank": 4,
                     "dynamic_lora_loading_path": str(tmp_path)})
    try:
        body = {"prompt": "hi", "max_tokens": 4}
        base = dep.completions(body)
        assert base["choices"][0]["finish_reason"] == "length"
        assert base["usage"]["completion_tokens"] == 4
        tuned = dep.completions({**body, "model": "tone"})
        assert tuned["model"] == "tone"
        assert tuned["choices"][0]["finish_reason"] == "length"
        assert tuned["usage"]["completion_tokens"] == 4
        assert tuned["choices"][0]["text"] != base["choices"][0]["text"]
        assert dep.completions(body)["choices"] == base["choices"]
        assert dep.engine.metrics["admission_failed"] == 0
    finally:
        dep.close()


@pytest.fixture(scope="module")
def deployment_with_a_narrow_adapter(tmp_path_factory):
    """``debug-128`` (hidden 128) served beside an adapter file made for
    ``debug`` (hidden 64), and no file at all for any other name."""
    from ray_tpu.llm.lora import save_adapter
    from ray_tpu.llm.serving import LLMDeployment

    path = tmp_path_factory.mktemp("adapters")
    save_adapter(str(path / "narrow.npz"),
                 _make_adapter(PRESETS["debug"], np.random.default_rng(3)))
    dep = LLMDeployment(
        "debug-128", max_slots=2, max_len=64, request_timeout_s=30,
        lora_config={"max_loras": 2, "max_rank": 4,
                     "dynamic_lora_loading_path": str(path)})
    yield dep
    dep.close()


@pytest.mark.parametrize("stream", [False, True], ids=["blocking", "streaming"])
@pytest.mark.parametrize("model", ["narrow", "absent"])
def test_a_request_the_engine_refuses_at_admission_reaches_its_caller_at_once(
        deployment_with_a_narrow_adapter, model, stream):
    """An adapter whose file has the wrong widths, and one with no file:
    the engine settles both at admission, and the waiter hears of it
    through the event path a finished request takes, as an error and
    not as an empty completion after ``request_timeout_s``."""
    from ray_tpu.llm.engine import AdmissionFailed

    dep = deployment_with_a_narrow_adapter
    engine, body = dep.engine, {"prompt": "hi", "max_tokens": 4}
    base = dep.completions(body)
    assert base["usage"]["completion_tokens"] == 4
    free = (engine.allocator.available(), len(engine._free_slots))
    refused = engine.metrics["admission_failed"]
    t0 = time.monotonic()
    with pytest.raises(AdmissionFailed, match=model) as caught:
        out = dep.completions({**body, "model": model, "stream": stream})
        if stream:
            list(out)
    assert time.monotonic() - t0 < 5.0
    assert caught.value.http_status.startswith("400")
    assert engine.metrics["admission_failed"] == refused + 1
    # the pages it took are back, no slot was held, nobody waits
    assert (engine.allocator.available(), len(engine._free_slots)) == free
    assert not engine.has_work and not dep._events and not dep._token_queues
    assert dep.completions(body)["choices"] == base["choices"]
