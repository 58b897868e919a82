"""Flagship model: forward shape/grad sanity and sharded train-step compile
on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import LlamaConfig, PRESETS, forward, init_params, loss_fn, param_axes
from ray_tpu.parallel import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import shard_params


def test_forward_shapes_and_finite():
    cfg = PRESETS["debug"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_loss_decreases_under_sgd():
    cfg = LlamaConfig(vocab_size=64, hidden=32, n_layers=2, n_heads=2,
                      n_kv_heads=1, intermediate=64, head_dim=16,
                      dtype=jnp.float32, attn_impl="reference", remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(lambda p_: loss_fn(p_, batch, cfg))(p)
        return l, jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    l0, params = step(params)
    for _ in range(5):
        l1, params = step(params)
    assert float(l1) < float(l0)


def test_sharded_train_step_on_mesh():
    """DP×TP×SP sharded loss+grad compiles and runs on the CPU mesh."""
    mesh = create_mesh(MeshConfig(dp=2, tp=2, sp=2))
    cfg = PRESETS["debug-128"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = shard_params(params, param_axes(cfg), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)

    @jax.jit
    def step(p, toks):
        return jax.value_and_grad(
            lambda p_: loss_fn(p_, {"tokens": toks}, cfg, mesh=mesh)
        )(p)

    loss, grads = step(params, tokens)
    assert np.isfinite(float(loss))
    flat, _ = jax.tree.flatten(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)


def test_ring_attention_model_matches_flash():
    mesh = create_mesh(MeshConfig(dp=2, sp=4))
    base = PRESETS["debug-128"]
    import dataclasses
    cfg_ring = dataclasses.replace(base, attn_impl="ring", dtype=jnp.float32)
    cfg_ref = dataclasses.replace(base, attn_impl="reference", dtype=jnp.float32)
    params = init_params(cfg_ref, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, base.vocab_size)
    ref = forward(params, tokens, cfg_ref)
    ring = forward(params, tokens, cfg_ring, mesh=mesh)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), atol=1e-4, rtol=1e-4)
