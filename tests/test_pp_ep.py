"""Pipeline (pp) and expert (ep) parallelism: numerical equivalence on the
virtual CPU mesh (SURVEY §7.2-6; the reference delegates both to vLLM,
``vllm_models.py:117-168``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import PRESETS, init_params, loss_fn, param_axes
from ray_tpu.models.llama import forward_hidden
from ray_tpu.models.moe import init_moe_params, moe_block
from ray_tpu.parallel import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import shard_params

CFG = dataclasses.replace(
    PRESETS["debug"], attn_impl="reference", dtype=jnp.float32, remat=False,
    pipeline_microbatches=2,
)


def test_pipeline_forward_matches_scan():
    mesh = create_mesh(MeshConfig(pp=2, dp=2, fsdp=2))
    params = init_params(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, CFG.vocab_size)
    ref = forward_hidden(params, tokens, CFG, mesh=None)
    sharded = shard_params(params, param_axes(CFG), mesh)
    out = jax.jit(lambda p, t: forward_hidden(p, t, CFG, mesh=mesh))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_pipeline_grads_match():
    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    cfg = dataclasses.replace(CFG, n_layers=4)  # one layer per stage
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}

    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, mesh=None)
    )(params)
    sharded = shard_params(params, param_axes(cfg), mesh)
    pp_loss, pp_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, mesh=mesh))
    )(sharded)
    np.testing.assert_allclose(float(pp_loss), float(ref_loss), rtol=1e-4)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat_pp = jax.tree_util.tree_leaves(pp_grads)
    for a, b in zip(flat_pp, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


def _moe_reference(x, params, top_k):
    """Per-token loop reference for the routed MLP (renormalised gates)."""
    b, s, e = x.shape
    tokens = np.asarray(x, np.float32).reshape(-1, e)
    router = np.asarray(params["router"], np.float32)
    logits = tokens @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(tokens)
    for i, row in enumerate(probs):
        idx = np.argsort(-row)[:top_k]
        gates = row[idx] / row[idx].sum()
        for g, xi in zip(gates, idx):
            h = tokens[i] @ np.asarray(params["w_gate"][xi], np.float32)
            u = tokens[i] @ np.asarray(params["w_up"][xi], np.float32)
            act = (h / (1 + np.exp(-h))) * u
            out[i] += g * (act @ np.asarray(params["w_down"][xi], np.float32))
    return out.reshape(b, s, e)


def test_moe_block_matches_reference():
    key = jax.random.PRNGKey(0)
    params = init_moe_params(key, hidden=16, expert_mlp=32, n_experts=4, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out, aux = moe_block(x, params, top_k=2, norm_topk=True)
    assert float(aux["load_balance"]) >= 1.0
    assert int(aux["dropped"]) == 0 and int(aux["rows"].sum()) == 2 * 8 * 2
    ref = _moe_reference(x, params, top_k=2)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_moe_llama_trains_on_ep_mesh():
    """MoE llama preset: jit path with experts sharded over ep."""
    mesh = create_mesh(MeshConfig(ep=2, dp=2, fsdp=2))
    cfg = dataclasses.replace(
        PRESETS["llama-moe-debug"], attn_impl="reference", dtype=jnp.float32, remat=False
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = shard_params(params, param_axes(cfg), mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}
    loss, grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, mesh=mesh))
    )(params)
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads))


def test_pp_ep_composed():
    """Pipeline over pp with MoE experts sharded over ep inside the
    shard_map — the composed strategy the dryrun exercises."""
    mesh = create_mesh(MeshConfig(pp=2, ep=2, dp=2))
    cfg = dataclasses.replace(
        PRESETS["llama-moe-debug"], attn_impl="reference", dtype=jnp.float32,
        remat=False, pipeline_microbatches=2,
        # the dispatch is dropless, so a microbatch routes as the whole does
        # the pipelined path does not thread the aux loss yet; zero it for
        # exact equivalence with the scan path
        moe_aux_weight=0.0,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)}
    ref_loss = loss_fn(params, batch, cfg, mesh=None)
    sharded = shard_params(params, param_axes(cfg), mesh)
    loss = jax.jit(lambda p: loss_fn(p, batch, cfg, mesh=mesh))(sharded)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)


def test_moe_aux_loss_applied_in_loss():
    """The load-balancing aux term must reach the training loss."""
    cfg = dataclasses.replace(
        PRESETS["llama-moe-debug"], attn_impl="reference", dtype=jnp.float32, remat=False
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)}
    base = float(loss_fn(params, batch, dataclasses.replace(cfg, moe_aux_weight=0.0)))
    weighted = float(loss_fn(params, batch, dataclasses.replace(cfg, moe_aux_weight=0.1)))
    assert weighted > base  # aux >= 1 by Cauchy-Schwarz, so weight must raise loss
