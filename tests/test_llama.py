"""Flagship model: forward shape/grad sanity and sharded train-step compile
on the 8-device CPU mesh."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("policy,mesh_axes,attn_impl,n_fwd", [
    ("attn", None, "flash", 1), ("full", None, "flash", 2), ("dots", None, "flash", 2),
    # the fsdp/tp path runs the same custom_vjp inside a shard_map body:
    # the names given there still reach the policy
    ("attn", {"dp": 2, "fsdp": 2, "tp": 2}, "flash", 1),
    ("full", {"dp": 2, "fsdp": 2, "tp": 2}, "flash", 2),
    # ulysses calls flash_attention per shard, between two all-to-alls
    ("attn", {"dp": 2, "sp": 4}, "ulysses", 1),
], ids=["attn", "full", "dots", "attn-shard_map", "full-shard_map", "attn-ulysses"])
def test_remat_policy_decides_how_often_flash_fwd_runs(policy, mesh_axes, attn_impl, n_fwd):
    """Under ``attn`` the flash rule's residuals (``attn_out``, compact
    ``attn_lse``) are saved names, so the backward pass does not re-run
    the forward kernel; ``full`` saves nothing and ``dots`` no kernel
    output, so both recompute it."""
    from ray_tpu.models.llama import _apply_remat, _block

    cfg = dataclasses.replace(PRESETS["debug-128"], remat=True, remat_policy=policy,
                              attn_impl=attn_impl)
    mesh = create_mesh(MeshConfig(**mesh_axes)) if mesh_axes else None
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    b, s = 4, 128
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, bt: loss_fn(p, bt, cfg, mesh=mesh)))(params, batch))
    calls = {n: len(re.findall(rf"name={n}\b", text))
             for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv")}
    assert calls == {"flash_fwd": n_fwd, "flash_bwd_dq": 1, "flash_bwd_dkdv": 1}
    if policy != "attn" or mesh is not None:
        return
    # what one remat'd block keeps for its backward pass
    try:
        from jax.ad_checkpoint import saved_residuals
    except ImportError:  # not re-exported by this jax
        from jax._src.ad_checkpoint import saved_residuals
    block = _apply_remat(functools.partial(
        _block, positions=jnp.arange(s, dtype=jnp.int32), config=cfg, mesh=None), cfg)
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         params["layers"])
    x = jax.ShapeDtypeStruct((b, s, cfg.hidden), cfg.dtype)
    saved = [(aval.str_short(short_dtypes=True), why) for aval, why in saved_residuals(
        lambda x_, l_: block(x_, l_)[0].astype(jnp.float32).sum(), x, layer)
        if "from the argument" not in why]
    assert [a for a, why in saved if "attn_lse" in why] == [f"f32[{b},{cfg.n_heads},{s}]"]
    # q and the kernel's output have o's shape: a second saved copy of o
    # (a name on the block's side too) would make three
    o_shape = f"bf16[{b},{cfg.n_heads},{s},{cfg.head_dim}]"
    assert [a for a, _ in saved].count(o_shape) == 2, saved
    assert f"f32[{b},{cfg.n_heads},{s},128]" not in [a for a, _ in saved]  # lanes-replicated
