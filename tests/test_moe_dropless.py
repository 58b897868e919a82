"""The dropless routed MLP (models/moe.py over ops/grouped_matmul.py) against
the plain float32 reference (benchmark/reference/moe_decoder.py: every expert
on every token, masked), at a small size on the CPU. No cluster is started
here (ROADMAP D2: parity tests share no cluster with a chaos test)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import moe_decoder
from ray_tpu.models import LlamaConfig, forward, init_params, loss_fn
from ray_tpu.models.moe import init_moe_params, moe_block
from ray_tpu.ops.grouped_matmul import grouped_matmul

BASE = LlamaConfig(
    vocab_size=128, hidden=64, n_layers=2, n_heads=4, n_kv_heads=4, intermediate=32,
    head_dim=16, rope_theta=10_000.0, dtype=jnp.float32, remat_policy="attn",
    moe_experts=8, moe_aux_weight=0.01, moe_z_weight=0.001)


def _model(top_k, qk_norm, norm_topk, seed=0):
    cfg = dataclasses.replace(BASE, moe_top_k=top_k, qk_norm=qk_norm,
                              moe_norm_topk=norm_topk)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    if qk_norm:  # a weight of all ones would hide a norm that forgets its weight
        for i, name in enumerate(("q_norm", "k_norm")):
            w = params["layers"][name]
            params["layers"][name] = w + 0.2 * jax.random.normal(
                jax.random.PRNGKey(7 + i), w.shape)
    arch = dict(rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, top_k=top_k,
                norm_topk=norm_topk)
    return cfg, params, arch


@pytest.mark.parametrize("top_k,qk_norm,norm_topk", [
    (2, True, False), (3, False, False), (2, False, True), (3, True, True)])
def test_logits_loss_and_every_gradient_match_the_reference(top_k, qk_norm, norm_topk):
    cfg, params, arch = _model(top_k, qk_norm, norm_topk)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    # each whole-stack pass, the reference's too, is one program, not an eager op at a time
    row_logits = jax.jit(lambda p, row: moe_decoder.logits(p, row, **arch)[0])
    want = jnp.stack([row_logits(params, row) for row in tokens])
    np.testing.assert_allclose(jax.jit(lambda p: forward(p, tokens, cfg))(params), want, atol=2e-5)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg, return_aux=True),
        has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: moe_decoder.loss(
        p, tokens, aux_weight=cfg.moe_aux_weight, z_weight=cfg.moe_z_weight, **arch)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    assert aux["rows_per_expert"].shape == (cfg.n_layers, cfg.moe_experts)
    assert int(aux["rows_dropped"]) == 0
    assert np.all(np.asarray(aux["rows_per_expert"]).sum(-1) == tokens.size * top_k)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(got, ref, atol=2e-6, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_gates_are_not_renormalised_unless_the_architecture_says_so():
    """OLMoE's gates are the softmax's own values: with 8 experts and top-2
    they sum to well under 1, so the two rules give different outputs."""
    params = init_moe_params(jax.random.PRNGKey(0), 64, 32, 8, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    plain, _ = moe_block(x, params, top_k=2, norm_topk=False)
    renorm, _ = moe_block(x, params, top_k=2, norm_topk=True)
    for norm_topk, got in ((False, plain), (True, renorm)):
        want, _ = moe_decoder.expert_layer(x[0], params, top_k=2, norm_topk=norm_topk)
        np.testing.assert_allclose(got[0], want, atol=2e-6)
    assert float(jnp.abs(plain - renorm).max()) > 10 * 2e-6


def test_a_batch_routed_almost_wholly_to_one_expert_computes_every_row():
    """Every token's first choice is expert 0 (the router's first column is
    aligned with a component all tokens share): 256 of the 512 rows land on
    one expert, four times a capacity factor of 1.25 would have kept, and
    all are computed."""
    params = init_moe_params(jax.random.PRNGKey(0), 64, 32, 8, jnp.float32)
    shared = jnp.ones((64,)) / 8.0
    params["router"] = params["router"].at[:, 0].set(4.0 * shared)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64)) + 8.0 * shared
    out, aux = moe_block(x, params, top_k=2, norm_topk=False)
    rows = np.asarray(aux["rows"])
    assert rows[0] == 256 and rows.sum() == 512 and int(aux["dropped"]) == 0
    want, _ = moe_decoder.expert_layer(x.reshape(256, 64), params, top_k=2, norm_topk=False)
    np.testing.assert_allclose(out.reshape(256, 64), want, atol=1e-5)


def _loop(lhs, rhs, sizes):
    out, start = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    for g, size in enumerate(sizes):
        out[start:start + size] = np.asarray(lhs[start:start + size]) @ np.asarray(rhs[g])
        start += size
    return out


@pytest.mark.parametrize("sizes,path", [
    ([10, 0, 30, 24], "interpret"),   # an empty group, boundaries inside tiles
    ([64, 0, 0, 0], "interpret"),     # one group holds everything
    ([0, 0, 0, 64], "interpret"),     # ... the last one
    ([7, 9, 1, 31], "interpret"),     # 48 rows: three tiles of 16, none aligned
    ([7, 9, 1, 1013], "ragged_dot"),  # 1030 rows: no tile divides them
], ids=["empty", "all-in-first", "all-in-last", "unaligned", "no-tile"])
def test_grouped_matmul_and_its_vjp_against_a_loop_over_groups(sizes, path):
    from ray_tpu.ops import trace_log

    m, k, n = sum(sizes), 32, 48
    keys = jax.random.split(jax.random.PRNGKey(m), 3)
    lhs = jax.random.normal(keys[0], (m, k))
    rhs = jax.random.normal(keys[1], (len(sizes), k, n))
    weight = jax.random.normal(keys[2], (m, n))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    before = trace_log.kernel_traces()
    fn = lambda l, r: grouped_matmul(  # noqa: E731
        l, r, group_sizes, gmm_tiles=(16, 32, 48), tgmm_tiles=(16, 32, 48))
    np.testing.assert_allclose(fn(lhs, rhs), _loop(lhs, rhs, sizes), atol=1e-4)
    d_lhs, d_rhs = jax.grad(lambda l, r: (fn(l, r) * weight).sum(), argnums=(0, 1))(lhs, rhs)
    starts = np.cumsum([0] + sizes[:-1])
    want_l, want_r = jax.grad(lambda l, r: sum(
        ((l[s:s + z] @ r[g]) * weight[s:s + z]).sum()
        for g, (s, z) in enumerate(zip(starts, sizes))), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(d_lhs, want_l, atol=1e-4)
    np.testing.assert_allclose(d_rhs, want_r, atol=1e-4)
    after = trace_log.kernel_traces()
    for kernel in ("moe_gmm", "moe_tgmm"):  # which path was taken is on record
        key = f"{kernel}:{path}"
        assert after.get(key, 0) > before.get(key, 0)


def test_two_expert_shards_equal_one():
    """``ep_axis``: each of two devices holds four consecutive experts and
    computes its own range of the sorted rows; the psum equals one device's
    result, and so do the gradients."""
    from jax.sharding import Mesh, PartitionSpec as P

    params = init_moe_params(jax.random.PRNGKey(0), 64, 32, 8, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    specs = {"router": P(), "w_gate": P("ep"), "w_up": P("ep"), "w_down": P("ep")}

    def sharded(x, params):
        return jax.shard_map(
            lambda x, p: moe_block(x, p, top_k=3, norm_topk=False, ep_axis="ep")[0],
            mesh=mesh, in_specs=(P(), specs), out_specs=P(), check_vma=False)(x, params)

    def single(x, params):
        return moe_block(x, params, top_k=3, norm_topk=False)[0]

    np.testing.assert_allclose(jax.jit(sharded)(x, params), single(x, params), atol=1e-5)
    loss = lambda f: (lambda x, p: jnp.sum(jnp.square(f(x, p))))  # noqa: E731
    got = jax.jit(jax.grad(loss(sharded), argnums=(0, 1)))(x, params)
    want = jax.jit(jax.grad(loss(single), argnums=(0, 1)))(x, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("policy", ["attn", "full"])
def test_the_step_holds_the_expected_grouped_matmul_calls(policy):
    """In ``grad(loss_fn)``'s jaxpr (the scanned block appears once forward
    and once backward): three ``moe_gmm`` forward; gate and up again in the
    backward pass (the down projection's output is no residual, since the
    gate multiplies its INPUT) and three for the inputs' gradients; three
    ``moe_tgmm`` for the weights'. Remat ``attn`` saves the routing, so the
    backward pass does not sort again; ``full`` sorts twice more."""
    cfg, params, _ = _model(2, True, False)
    cfg = dataclasses.replace(cfg, remat_policy=policy)
    tokens = jnp.zeros((2, 16), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg)))(params))
    count = lambda name: len(re.findall(rf"\bname={name}\b", text))  # noqa: E731
    assert (count("moe_gmm"), count("moe_tgmm")) == (8, 3)
    assert len(re.findall(r"\bsort\[", text)) == (2 if policy == "attn" else 4)
