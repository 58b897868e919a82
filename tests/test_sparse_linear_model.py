"""Block-selected sparse attention beside lightning linear attention under the
three fixed multipliers (``sparse-linear-debug``), the program in float32
against the plain reference (``benchmark/reference/sparse_linear_decoder.py``)
on seeded weights: each layer kind alone with gradients, the logits, the loss,
one adafactor step; the multipliers each shown to matter; the decays by
published index under a cut."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import sparse_linear_decoder as ref
from benchmark.runners.train_hybrid import seed_norms
from ray_tpu.models import forward, init_params, llama, loss_fn, param_axes, update_buffers
from ray_tpu.models.block_sparse import block_sparse_mixer, mean_set_keys
from ray_tpu.models.lightning import LightningAttention, lightning_mixer, log_decays
from ray_tpu.ops.lightning_attention import lightning_scan

CFG = dataclasses.replace(llama.PRESETS["sparse-linear-debug"], dtype=jnp.float32)
SEQ = 160     # ten blocks of 16 keys: a query keeps 4; 40 pooled keys; two chunks of 128


def arch_of(cfg):
    a, b = cfg.block_sparse, cfg.lightning
    return dict(
        kinds={"block_sparse": dict(heads=a.heads, kv_heads=a.kv_heads, head_dim=a.head_dim,
                                    **a.sizes),
               "lightning": dict(heads=b.heads, head_dim=b.head_dim, rope_theta=b.rope_theta,
                                 depth=b.depth)},
        pattern=cfg.layer_pattern, lead_pattern=(), layer_ids=cfg.layer_ids,
        norm_eps=cfg.norm_eps, embed_scale=cfg.embed_scale,
        residual_scale=cfg.residual_scale, logit_scale=cfg.logit_scale)


ARCH = arch_of(CFG)


@pytest.fixture(scope="module")
def params():
    key = jax.random.PRNGKey(0)
    return seed_norms(init_params(CFG, key), key)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, CFG.vocab_size)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _layer(params, slot, period=1):
    return jax.tree.map(lambda a: a[period], params["layers"][slot])


def _inputs():
    h, g = (jax.random.normal(jax.random.PRNGKey(i), (SEQ, CFG.hidden), jnp.float32)
            for i in (2, 3))
    return h, g, jnp.arange(SEQ, dtype=jnp.int32)


def test_the_tree_matches_its_axes_and_the_decays_are_the_published_layers(params):
    axes = param_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    # the stack's layers 1 and 3 are the published layers 1 and 4 of 6
    decay = params["layers"]["slot1"]["log_decay"]
    assert decay.shape == (2, 4) and decay.dtype == jnp.float32
    slopes = 2.0 ** (-8.0 * np.arange(1, 5) / 4)
    for period, published in enumerate((1, 4)):
        want = -slopes * (1 - published / 5 + 1e-5)
        np.testing.assert_allclose(decay[period], want, rtol=1e-6)
        np.testing.assert_allclose(np.exp(decay[period]), ref.decays(
            ARCH["kinds"]["lightning"], published), rtol=1e-6)
    # without ``layer_ids`` a layer's index is its own; without the layer
    # factor every layer is layer 0
    own = init_params(dataclasses.replace(CFG, layer_ids=()), jax.random.PRNGKey(0))
    np.testing.assert_allclose(own["layers"]["slot1"]["log_decay"][1],
                               -slopes * (1 - 3 / 5 + 1e-5), rtol=1e-6)
    flat = log_decays(LightningAttention(4, 16, 1e4, depth=10**9), [1, 4])   # no layer factor
    np.testing.assert_allclose(flat, np.tile(-slopes * (1 + 1e-5), (2, 1)), rtol=1e-6)
    with pytest.raises(ValueError, match="layer ids"):
        dataclasses.replace(CFG, layer_ids=(0, 1))


def test_the_lightning_layer_alone_with_gradients(params):
    h, g, positions = _inputs()
    layer = _layer(params, "slot1")

    def program(h, w):
        return lightning_mixer(h[None], w, config=CFG, positions=positions)[0]

    def reference(h, w):
        return ref.lightning_mixer(h, w, ARCH["kinds"]["lightning"], 4, CFG.norm_eps)

    got, pull = jax.vjp(program, h, layer)
    want, ref_pull = jax.vjp(reference, h, layer)
    assert _rel(got, want) < 2e-5
    (dh, dw), (ref_dh, ref_dw) = pull(g), ref_pull(g)
    assert _rel(dh, ref_dh) < 2e-5
    for name in ref_dw:
        if name in ("wq", "wk", "wv", "wo", "w_attn_gate", "q_norm", "k_norm", "o_norm"):
            assert _rel(dw[name], ref_dw[name]) < 5e-5, name
    assert not np.any(np.asarray(dw["log_decay"]))
    # the kernels swapped for the scan over positions: the same layer
    scanned = lightning_mixer(h[None], layer, config=CFG, positions=positions,
                              scan=lightning_scan)[0]
    assert _rel(got, scanned) < 2e-5
    # another published index is another layer
    assert _rel(got, ref.lightning_mixer(h, layer, ARCH["kinds"]["lightning"], 1,
                                         CFG.norm_eps)) > 0.05


def test_the_block_selected_layer_alone_with_gradients(params):
    h, g, positions = _inputs()
    layer = _layer(params, "slot0")
    spec = ARCH["kinds"]["block_sparse"]

    def program(h, w):
        y, aux = block_sparse_mixer(h[None], w, config=CFG, positions=positions,
                                    return_selection=True)
        return y[0], aux

    got, pull, aux = jax.vjp(program, h, layer, has_aux=True)
    sets = aux["selection"][0]
    want, ref_pull, own = jax.vjp(
        lambda h, w: ref.sparse_mixer(h, w, spec, CFG.norm_eps, sets), h, layer, has_aux=True)
    assert ref.sets_agreement(np.asarray(own), np.asarray(sets)) == {"sets": 1.0, "flags": 1.0}
    assert _rel(got, want) < 2e-5
    (dh, dw), (ref_dh, ref_dw) = pull(g), ref_pull(g)
    assert _rel(dh, ref_dh) < 2e-5
    for name in ("wq", "wk", "wv", "wo", "w_attn_gate", "q_norm", "k_norm"):
        assert _rel(dw[name], ref_dw[name]) < 5e-5, name
    # from position 64 on a query drops blocks: the layer is not dense attention
    assert np.asarray(sets).sum(-1).max() == 4 and sets.shape == (2, SEQ, SEQ // 16)
    kept = float(aux["block_kept_share"])
    assert kept == pytest.approx(mean_set_keys(CFG.block_sparse, SEQ) * SEQ / (SEQ * (SEQ + 1) / 2),
                                 rel=1e-6) and kept < 0.6
    whole = dict(spec, topk=1 << 20)
    assert _rel(ref.sparse_mixer(h, layer, whole, CFG.norm_eps)[0], want) > 0.01
    # a row no block divides: whole blocks inside, the row's own length outside
    odd = block_sparse_mixer(h[None, :150], layer, config=CFG, positions=positions[:150])[0][0]
    assert odd.shape == (150, CFG.hidden) and _rel(odd, got[:150]) < 1e-5


def test_a_row_shorter_than_top_k_blocks_is_dense_causal_attention(params):
    h, _, positions = _inputs()
    layer = _layer(params, "slot0")
    y, aux = block_sparse_mixer(h[None, :64], layer, config=CFG, positions=positions[:64])
    assert float(aux["block_kept_share"]) == pytest.approx(1.0, abs=1e-6)
    whole = dict(ARCH["kinds"]["block_sparse"], topk=1 << 20)
    assert _rel(y[0], ref.sparse_mixer(h[:64], layer, whole, CFG.norm_eps)[0]) < 2e-5


def test_logits_loss_and_the_selection_against_the_reference(params, tokens):
    # each whole-stack pass, the reference's too, is one program a shape
    hidden, aux = jax.jit(lambda p: llama.forward_hidden(
        p, tokens, CFG, return_aux=True, return_selection=True))(params)
    sets = jnp.moveaxis(aux["selection"], 1, 0)           # [rows, layers, KV, S, NB]
    assert sets.shape == (2, 2, 2, SEQ, SEQ // 16)
    logits = jax.jit(lambda p: forward(p, tokens, CFG))(params)
    given = jax.jit(lambda p, row, chosen: ref.logits(p, row, ARCH, chosen))
    sorted_ = jax.jit(lambda p, row: ref.logits(p, row, ARCH)[0])
    for row in range(2):
        want, own = given(params, tokens[row], sets[row])
        assert float(ref.position_errors(logits[row], want).max()) < 2e-5
        assert ref.sets_agreement(np.asarray(own[0]), np.asarray(sets[row, 0]))["sets"] == 1.0
        # its own selection, by a sort: the same logits
        assert float(ref.position_errors(logits[row], sorted_(params, tokens[row])).max()) < 2e-5
    loss, counted = jax.jit(lambda p: loss_fn(
        p, {"tokens": tokens}, CFG, chunk_tokens=64, return_aux=True))(params)
    want = jax.jit(lambda p: ref.loss(p, tokens, ARCH, sets))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(counted["attn_block_tile_share"]) == 1.0
    assert 0 < float(counted["attn_block_forced_share"]) < 1
    assert update_buffers(params, counted, CFG) is params


def test_one_adafactor_step_and_the_block_at_a_time_gradient(params, tokens):
    tokens = tokens[:1]     # one row: the by-hand pass compiles a program a block
    # each whole-stack pass is one program, not an eager op at a time
    _, aux = jax.jit(lambda p: llama.forward_hidden(
        p, tokens, CFG, return_aux=True, return_selection=True))(params)
    sets = jnp.moveaxis(aux["selection"], 1, 0)
    grads = jax.jit(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=64)))(params)
    ref_loss, seen, by_name = ref.loss_and_grads(params, tokens, ARCH, sets)
    whole = jax.jit(jax.grad(lambda p: ref.loss(p, tokens, ARCH, sets)))(params)
    named = lambda tree: {jax.tree_util.keystr(k): v for k, v in  # noqa: E731
                          jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert seen["own_sets"].shape == (2, 2, SEQ, SEQ // 16)    # [layers, KV, S, NB]
    for name, leaf in named(grads).items():
        if name.endswith("['log_decay']"):
            assert not np.any(np.asarray(leaf)) and not np.any(np.asarray(by_name[name]))
            continue
        assert _rel(leaf, by_name[name]) < 5e-5, name
        assert _rel(named(whole)[name], by_name[name]) < 5e-5, name
    opt = optax.adafactor(0.001)
    step = jax.jit(lambda p, g: optax.apply_updates(p, opt.update(g, opt.init(p), p)[0]))
    got, want = step(params, grads), step(params, jax.tree_util.tree_unflatten(
        jax.tree.structure(params), [by_name[n] for n in named(params)]))
    for name, leaf in named(got).items():
        moved = np.asarray(leaf) - np.asarray(named(params)[name])
        if name.endswith("['log_decay']"):
            assert not np.any(moved)        # no gradient moves the decays
        else:
            assert _rel(moved, np.asarray(named(want)[name]) - np.asarray(
                named(params)[name])) < 1e-3, name


@pytest.mark.parametrize("field", ["embed_scale", "residual_scale", "logit_scale"])
def test_each_multiplier_matters_and_at_one_the_program_is_what_it_was(params, tokens, field):
    logits = forward(params, tokens[:1], CFG)[0]
    without = forward(params, tokens[:1], dataclasses.replace(CFG, **{field: 1.0}))[0]
    assert float(ref.position_errors(without, logits).max()) > 0.05
    want = ref.logits(params, tokens[0], {**ARCH, field: 1.0})[0]
    assert float(ref.position_errors(without, want).max()) < 2e-5
    # all three at 1: a lowered step mentions no multiplier's product
    plain = dataclasses.replace(llama.PRESETS["debug"], dtype=jnp.float32)
    assert (plain.embed_scale, plain.residual_scale, plain.logit_scale) == (1.0, 1.0, 1.0)
    assert llama._scaled(logits, 1.0) is logits
