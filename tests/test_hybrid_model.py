"""The hybrid decoder on the normal path (``models/llama.py`` as a scan over
periods of layer kinds) against its plain reference
(``benchmark/reference/hybrid_decoder.py``), at a small size on the CPU."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_decoder as ref
from ray_tpu.models import gdn, moe
from ray_tpu.models.llama import (ATTN, DENSE, MIXERS, PRESETS, LlamaConfig, forward,
                                  init_params, loss_fn, param_axes, train_flops_per_token)
from ray_tpu.ops import apply_rope, rms_norm

CFG = dataclasses.replace(PRESETS["hybrid-debug"], dtype=jnp.float32, remat_policy="attn")
ARCH = dict(pattern=CFG.layer_pattern, rope_theta=CFG.rope_theta, rotary_dim=CFG.rotary_dim,
            norm_eps=CFG.norm_eps, key_heads=CFG.gdn_key_heads,
            value_heads=CFG.gdn_value_heads, top_k=CFG.moe_top_k, norm_topk=True,
            held_first=CFG.moe_held[0])


def seeded(cfg, seed=0):
    """Weights with every norm weight moved off its start, so a norm left
    out or ``w`` read for ``1 + w`` shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed))

    def move(path, leaf):
        if not str(path[-1].key).endswith("norm"):
            return leaf
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 1), sum(map(ord, jax.tree_util.keystr(path))))
        return leaf + jax.random.uniform(k, leaf.shape, minval=-0.5, maxval=0.5)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def model():
    params = seeded(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, CFG.vocab_size)
    return params, tokens


@pytest.fixture(scope="module")
def compiled_step(model):
    """The program's loss with everything counted beside it, and every
    gradient, compiled once: its outputs are ``program_step``."""
    params, tokens = model
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=64, return_aux=True),
            has_aux=True)).lower(params).compile()


@pytest.fixture(scope="module")
def program_step(model, compiled_step):
    return compiled_step(model[0])


def pick(params, slot):
    return jax.tree.map(lambda a: a[0], params["layers"][slot])


def test_logits_and_loss_match_the_reference(model, program_step):
    params, tokens = model
    (loss, aux), _ = program_step
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: forward(p, tokens, CFG))(params)
    # the reference as one program a call, not an eager op at a time
    logits = jax.jit(lambda p, row: ref.logits(p, row, **ARCH)[0])
    for i in range(tokens.shape[0]):
        assert float(ref.position_errors(got[i], logits(params, tokens[i])).max()) < 1e-4
    want = jax.jit(lambda p: ref.loss(p, tokens, aux_weight=CFG.moe_aux_weight, **ARCH))(params)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    # the counters: rows over all experts, over the held ones, nothing dropped
    n_rows = tokens.size * CFG.moe_top_k
    assert aux["rows_per_expert"].shape == (4, 8) and int(aux["rows_dropped"]) == 0
    assert (aux["rows_per_expert"].sum(-1) == n_rows).all()
    np.testing.assert_array_equal(aux["rows_per_held_expert"], aux["rows_per_expert"][:, :2])
    np.testing.assert_allclose(aux["held_share"], aux["rows_per_held_expert"].sum(-1) / n_rows, rtol=1e-6)


def test_every_gradient_leaf_matches_the_references(model, program_step):
    params, tokens = model
    _, got = program_step
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda p: ref.loss(p, tokens, aux_weight=CFG.moe_aux_weight, **ARCH)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 2 + 1 + 3 * 17 + 16
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.abs(b).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, atol=2e-3 * scale, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


def test_at_the_presets_own_width_the_held_ranges_adds_are_plain_ops_under_their_scopes(
        compiled_step):
    """A hidden width of 64 is no lane tile, so the held range's rows move by
    a gather and a scatter-add and not by ``moe_rows`` (at one lane tile:
    ``tests/test_device_scopes.py``): each of the four, the two of the
    backward rule too, carries its scope's path, as every gather and scatter
    of the step does."""
    text = compiled_step.as_text()
    # no op of the kernel (``take_rows`` and XLA's scatter-add live in
    # ``ops/moe_rows.py`` too, so the file's name is in the text)
    assert "/moe_rows/" not in text
    moved = set()
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = .*?\s(gather|scatter)\(", line)
        if not op:
            continue
        scope = re.search(r'rt_scope="([^"]*)"', line)
        assert scope, line
        by = re.search(r"(transpose\(jvp\(|jvp\(|/)(moe_dispatch|moe_combine)\)*/",
                       re.search(r'op_name="([^"]*)"', line).group(1))
        if by:
            moved.add((op.group(1), scope.group(1), by.group(1)))
    assert moved >= {("gather", "stack/mlp/moe_dispatch", "jvp("),
                     ("scatter", "stack/mlp/moe_dispatch", "transpose(jvp("),
                     ("scatter", "stack/mlp/moe_combine", "/"),
                     ("gather", "stack/mlp/moe_combine", "transpose(jvp(")}, moved


@pytest.mark.parametrize("impl", ["kernels", "jnp"])
def test_the_deltanet_mixer_matches_the_reference_in_both_forms(model, impl):
    params, _ = model
    layer = pick(params, "slot0")
    h = jax.random.normal(jax.random.PRNGKey(2), (192, CFG.hidden))
    scan = gdn.chunked_jnp if impl == "jnp" else None
    with jax.default_matmul_precision("highest"):
        got, seen = gdn.gdn_mixer(h[None], layer, config=CFG, scan=scan, return_scan=True)
    want, ref_seen = ref.gdn_mixer(h, layer, key_heads=CFG.gdn_key_heads,
                                   value_heads=CFG.gdn_value_heads, eps=CFG.norm_eps)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    for name in ("q", "k", "v", "g", "beta", "o"):
        np.testing.assert_allclose(seen[name][0], ref_seen[name], atol=2e-5, err_msg=name)


def test_the_gated_attention_mixer_matches_the_reference(model):
    params, _ = model
    layer = pick(params, "slot3")
    h = jax.random.normal(jax.random.PRNGKey(3), (128, CFG.hidden))
    with jax.default_matmul_precision("highest"):
        got = ATTN.apply(h[None], layer, config=CFG, positions=jnp.arange(128), mesh=None)
    want = ref.attn_mixer(h, layer, theta=CFG.rope_theta, eps=CFG.norm_eps,
                          rotary_dim=CFG.rotary_dim)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_partial_rope_turns_the_first_features_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 32))
    pos = jnp.arange(16)
    got = apply_rope(x, pos, theta=1e4, rotary_dim=8)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[..., :8], apply_rope(x[..., :8], pos, theta=1e4), atol=1e-6)
    np.testing.assert_array_equal(apply_rope(x, pos, theta=1e4, rotary_dim=32),
                                  apply_rope(x, pos, theta=1e4))


def test_the_offset_norm_multiplies_by_one_plus_w():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16,))
    np.testing.assert_allclose(rms_norm(x, w, offset=1.0), rms_norm(x, 1.0 + w), atol=1e-6)
    np.testing.assert_array_equal(rms_norm(x, w, offset=0.0), rms_norm(x, w))


def test_the_shares_add_up_to_the_uncut_layer():
    """Over the 2 ranges of 4 experts, the routed parts summed, plus the
    shared expert counted once, equal the uncut reference's layer; and the
    program's share equals the reference's share, range by range."""
    whole = dataclasses.replace(CFG, moe_held=None)
    layer = moe.MOE.init(whole, jax.random.split(jax.random.PRNGKey(5), 3), (), None)
    h = jax.random.normal(jax.random.PRNGKey(6), (96, CFG.hidden))
    kw = dict(top_k=CFG.moe_top_k, norm_topk=True)
    uncut, _ = ref.expert_layer(h, layer, first=0, **kw)
    shared_only = {k: v for k, v in layer.items() if "shared" in k}
    shared = moe.shared_expert(h, shared_only)
    total = jnp.zeros_like(uncut)
    with jax.default_matmul_precision("highest"):
        for first in (0, 4):
            part = {**layer, **{k: layer[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
            got, aux = moe.moe_block(h[None], part, held=(first, 4), **kw)
            want, _ = ref.expert_layer(h, part, first=first, **kw)
            np.testing.assert_allclose(got[0], want, atol=2e-5)
            np.testing.assert_array_equal(aux["rows_held"], aux["rows"][first:first + 4])
            total = total + got[0] - shared
        np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
        # the plain path with every expert held is the uncut layer too
        np.testing.assert_allclose(moe.moe_block(h[None], layer, **kw)[0][0], uncut, atol=2e-5)
    with pytest.raises(ValueError, match="the leaves hold"):
        moe.moe_block(h[None], layer, held=(0, 4), **kw)


def test_a_held_range_longer_than_the_compact_paths_bound_drops_no_row():
    """2 of 8 experts held: the compact path is compiled for half of all
    rows. A router that sends every token to the held pair makes the range
    all N k rows: the step takes the path that gathers every row, and the
    result is still the reference's; an even router takes the compact path."""
    cfg = dataclasses.replace(CFG, moe_held=(2, 2), moe_top_k=2)
    layer = moe.MOE.init(cfg, jax.random.split(jax.random.PRNGKey(7), 3), (), None)
    h = jax.random.normal(jax.random.PRNGKey(8), (64, CFG.hidden))
    assert moe._held_capacity(64 * 2, (2, 2), 8) == 64
    assert moe._held_capacity(64 * 2, (0, 4), 8) is None and moe._held_capacity(128, None, 8) is None
    kw = dict(top_k=2, norm_topk=True)
    for bias, share in ((0.0, None), (50.0, 1.0)):
        skewed = {**layer, "router": layer["router"].at[:, 2:4].add(bias * jnp.sign(h.mean(0))[:, None])}
        biased_h = h + (1.0 if bias else 0.0) * jnp.sign(h.mean(0))
        with jax.default_matmul_precision("highest"):
            got, aux = jax.jit(lambda h, w: moe.moe_block(h[None], w, held=(2, 2), **kw))(biased_h, skewed)
            grad = jax.grad(lambda w: moe.moe_block(biased_h[None], w, held=(2, 2), **kw)[0].sum())(skewed)
        want, _ = ref.expert_layer(biased_h, skewed, first=2, **kw)
        np.testing.assert_allclose(got[0], want, atol=5e-5)
        assert int(aux["dropped"]) == 0 and int(aux["rows"].sum()) == 128
        if share is not None:
            assert float(aux["held_share"]) == share       # past the bound of a half
        else:
            assert float(aux["held_share"]) < 0.5          # the compact path
        # and so are the gradients, through the backward rule's own cond
        want_grad = jax.grad(lambda w: ref.expert_layer(biased_h, w, first=2, **kw)[0].sum())(skewed)
        for name in ("w_gate", "w_up", "w_down", "router", "w_shared_down"):
            scale = float(jnp.abs(want_grad[name]).max())
            np.testing.assert_allclose(grad[name], want_grad[name], atol=1e-3 * scale,
                                       err_msg=f"{name} at bias {bias}")


def test_a_period_of_one_block_keeps_the_layout_and_the_draws_it_always_had():
    """Every configuration from before layer kinds is a period of one
    attention block: the leaves sit directly under ``layers``, stacked over
    all layers, and are the same draws from the same key."""
    for name in ("debug", "llama-moe-debug"):
        cfg = PRESETS[name]
        params, axes = init_params(cfg, jax.random.PRNGKey(0)), param_axes(cfg)
        assert set(params["layers"]) == set(axes["layers"])
        assert params["layers"]["wq"].shape == (cfg.n_layers, cfg.hidden, cfg.n_heads, cfg.head_dim)
        keys = jax.random.split(jax.random.PRNGKey(0), 9)
        want = (jax.random.truncated_normal(keys[1], -2, 2, params["layers"]["wq"].shape, jnp.float32)
                * cfg.hidden ** -0.5).astype(cfg.dtype)
        np.testing.assert_array_equal(params["layers"]["wq"], want)
        assert all(axes["layers"][k][0] == "layers" for k in axes["layers"])
    qk = dataclasses.replace(PRESETS["debug"], qk_norm=True)
    assert init_params(qk, jax.random.PRNGKey(0))["layers"]["q_norm"].shape == (2, 4 * 16)


def test_the_hybrid_tree_is_a_sub_tree_a_position_of_the_period():
    params, axes = init_params(CFG, jax.random.PRNGKey(0)), param_axes(CFG)
    assert list(params["layers"]) == ["slot0", "slot1", "slot2", "slot3"]
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    jax.tree.map(lambda a, x: None if a.ndim == len(x) else pytest.fail(str(x)),
                 params, axes, is_leaf=is_axes)
    assert "w_qkvz" in params["layers"]["slot0"] and "wq" in params["layers"]["slot3"]
    assert params["layers"]["slot3"]["wq"].shape == (1, 64, 4, 2 * 32)   # q | gate a head
    assert params["layers"]["slot0"]["w_gate"].shape == (1, 2, 64, 32)   # the 2 held of 8
    assert params["layers"]["slot0"]["router"].shape == (1, 64, 8)       # the router's width
    # (1 + w) norms start at 0, the gated norm at 1; different positions draw differently
    assert not params["layers"]["slot0"]["attn_norm"].any() and not params["final_norm"].any()
    assert (params["layers"]["slot0"]["gdn_norm"] == 1).all()
    assert (params["layers"]["slot0"]["w_out"] != params["layers"]["slot1"]["w_out"]).any()
    with pytest.raises(ValueError, match="whole number of periods"):
        dataclasses.replace(CFG, n_layers=6).n_periods


def test_the_decay_of_seeded_weights_lets_state_cross_chunks():
    layer = pick(init_params(CFG, jax.random.PRNGKey(0)), "slot0")
    step_decay = jnp.exp(-jnp.exp(layer["A_log"]) * jax.nn.softplus(layer["dt_bias"]))
    assert float(step_decay.min()) > 0.15 and float(step_decay.max()) < 1.0
    np.testing.assert_allclose(jax.nn.softplus(layer["dt_bias"]),
                               jnp.clip(jax.nn.softplus(layer["dt_bias"]), 1e-3, 0.1), rtol=1e-5)


def test_kinds_own_their_names_and_the_policy_saves_them():
    assert MIXERS["gdn"].save_names == gdn.SAVE_NAMES
    assert set(moe.ROUTE_NAMES) == set(moe.MOE.save_names) and DENSE.save_names == ()
    params, tokens = seeded(CFG), jnp.zeros((1, 64), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=64)))(params))
    for name in gdn.SAVE_NAMES + ("attn_gate", "attn_out", "moe_probs"):
        assert f"name={name}" in text, name
    # with its output and operands saved, the forward kernel runs once a layer
    assert text.count("name=gdn_fwd") == 3 and text.count("name=gdn_bwd") == 3
    # the chunk-local half runs again under remat for the operands ``gdn_bwd``
    # takes, and no chunk's inverse is saved for it
    assert text.count("name=gdn_wy_fwd") == 6 and text.count("name=gdn_wy_bwd") == 3
    assert "gdn_tinv" not in text
    assert text.count("name=flash_fwd") == 1


def test_the_elementwise_kernels_run_twice_forward_and_once_backward_a_layer(monkeypatch):
    """At a head width of 128 with the dispatch's predicate patched: the
    differentiated, remat-ed step holds ``gdn_conv_fwd`` and ``gdn_norm_fwd``
    twice a DeltaNet layer (forward, and again under remat) and each backward
    kernel once, and no float32 value of the projection's ``[B, S, 2 kw + vw]``
    outside a kernel (the plain path's conv writes one)."""
    cfg = dataclasses.replace(PRESETS["hybrid-debug"], remat_policy="attn", gdn_head_dim=128)
    params, tokens = init_params(cfg, jax.random.PRNGKey(0)), jnp.zeros((1, 64), jnp.int32)
    step = jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, cfg, chunk_tokens=64))
    wide = (2 * cfg.gdn_key_heads + cfg.gdn_value_heads) * 128
    monkeypatch.setattr(gdn, "_in_vmem", lambda c, rows: True)
    text = str(jax.make_jaxpr(step)(params))
    jax.clear_caches()       # the scanned, remat-ed block's trace is cached by its function
    for kernel in ("gdn_conv", "gdn_norm"):
        assert text.count(f"name={kernel}_fwd") == 6 and text.count(f"name={kernel}_bwd") == 3
    assert text.count("name=gdn_wy_fwd") == 6 and text.count("name=gdn_fwd") == 3
    # ``gdn_qkv`` itself is there in the model's type, and never in float32: a
    # kernel's own body holds its tiles, not the whole array
    assert f"bf16[1,64,{wide}]" in text and f"f32[1,64,{wide}]" not in text


def test_flops_are_the_kinds_own_and_dense_configs_count_as_before():
    c = PRESETS["llama3-1b"]
    n = c.n_layers * (c.hidden * c.head_dim * (2 * c.n_heads + 2 * c.n_kv_heads)
                      + 3 * c.hidden * c.intermediate) + c.hidden * c.vocab_size
    assert train_flops_per_token(c, 2048) == 6.0 * n + 6 * c.n_layers * c.n_heads * c.head_dim * 2048
    m = PRESETS["mixtral-8x7b-ish"]
    n = m.n_layers * (m.hidden * m.head_dim * (2 * m.n_heads + 2 * m.n_kv_heads)
                      + 2 * 3 * m.hidden * m.intermediate + m.hidden * 8) + m.hidden * m.vocab_size
    assert train_flops_per_token(m, 4096) == 6.0 * n + 6 * m.n_layers * m.n_heads * m.head_dim * 4096
    h = CFG
    expert = (h.hidden * 8 + 3 * h.hidden * 32 + h.hidden + 3 * (2 / 8) * 3 * h.hidden * 32)
    by_hand = 6.0 * (3 * (gdn.gdn_matmul_params(h) + expert)
                     + h.hidden * h.head_dim * (3 * 4 + 2 * 2) + expert
                     + h.hidden * h.vocab_size) \
        + 3 * (3 * gdn.gdn_mixing_flops(h, 128) + 2.0 * 4 * 32 * 128)
    assert train_flops_per_token(h, 128) == pytest.approx(by_hand, rel=1e-12)


def test_the_pipeline_takes_a_period_of_one_block_only():
    assert LlamaConfig().layer_pattern == ("attn",) and LlamaConfig().n_periods == 32
