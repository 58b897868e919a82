"""What the LLM engine's test files share: a tiny float32 model, the
ground truth of greedy decoding by the plain forward, and seeded LoRA
adapters with the merged weights that are their ground truth."""

import dataclasses
import functools
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import PRESETS, forward, init_params


def within(seconds, build, *args, **kwargs):
    """``build(...)`` on a daemon thread of its own, given ``seconds``. For a
    wait that no parameter bounds (a shard fleet's build waits 600 s on its
    actors): a hang fails the test here, in a minute, and says where, and the
    process can exit while the thread still waits."""
    settled = queue.SimpleQueue()

    def run():
        try:
            settled.put((build(*args, **kwargs), None))
        except BaseException as e:  # noqa: BLE001 - handed to the caller as it is
            settled.put((None, e))

    threading.Thread(target=run, daemon=True, name=f"within-{seconds}s").start()
    try:
        value, error = settled.get(timeout=seconds)
    except queue.Empty:
        name = getattr(build, "__qualname__", build)
        raise TimeoutError(f"{name} not done in {seconds} s") from None
    if error is not None:
        raise error
    return value


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(PRESETS["debug"], dtype=jnp.float32, attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@functools.lru_cache(maxsize=None)
def _forward_of(cfg):
    return jax.jit(lambda params, rows: forward(params, rows, cfg))


def naive_greedy(params, cfg, prompt, n):
    """Greedy decoding by the whole forward at every step. One program a
    width and not one a length: a causal model's logits at a position do not
    see the padding behind it."""
    toks, out = list(prompt), []
    width = -(-(len(toks) + n) // 64) * 64
    for _ in range(n):
        rows = jnp.asarray([toks + [0] * (width - len(toks))])
        t = int(jnp.argmax(_forward_of(cfg)(params, rows)[0, len(toks) - 1]))
        out.append(t)
        toks.append(t)
    return out


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


def _merge_adapter(cfg, params, arrays):
    """Base params with the adapter folded in (ground truth)."""
    import jax.numpy as jnp

    L, E, H, KH, D = (cfg.n_layers, cfg.hidden, cfg.n_heads,
                      cfg.n_kv_heads, cfg.head_dim)
    layers = dict(params["layers"])
    for p, heads in (("wq", H), ("wk", KH), ("wv", KH)):
        delta = np.einsum("ler,lro->leo", arrays[f"{p}.A"], arrays[f"{p}.B"])
        layers[p] = layers[p] + jnp.asarray(
            delta.reshape(L, E, heads, D), layers[p].dtype)
    delta_o = np.einsum("lfr,lre->lfe", arrays["wo.A"], arrays["wo.B"])
    layers["wo"] = layers["wo"] + jnp.asarray(
        delta_o.reshape(L, H, D, E), layers["wo"].dtype)
    return {**params, "layers": layers}
