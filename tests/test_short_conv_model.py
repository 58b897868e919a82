"""The double-gated short convolution (``models/short_conv.py``), two leading
dense layers under it, the period of roped per-head-normed attention and three
convs, the sigmoid router's held share and the tied table (``conv-moe-debug``,
Pallas interpreted on the CPU) against plain functions and against the plain
reference ``benchmark/reference/conv_moe_decoder.py`` on seeded float32
weights."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import conv_moe_decoder as ref
from benchmark.runners.train_conv import planted, seed_leaves
from ray_tpu.models import PRESETS, init_params, loss_fn, param_axes
from ray_tpu.models.llama import MIXERS, forward, train_flops_per_token
from ray_tpu.models.moe import moe_block
from ray_tpu.models.short_conv import SAVE_NAMES, gated_conv, sconv_mixer

# one period after the two leading layers: the CPU compiles every interpreted
# kernel call, and a gradient through ten layers is past a test's seconds
CFG = dataclasses.replace(PRESETS["conv-moe-debug"], n_layers=6, dtype=jnp.float32,
                          remat_policy="attn")
SEQ = 40
# float32 program against the float32 reference: rounding alone
TIGHT = 2e-5


def arch_of(cfg, *faults) -> dict:
    return dict(pattern=cfg.layer_pattern, lead_pattern=cfg.lead_pattern,
                attn=dict(heads=cfg.n_heads, kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                          rope_theta=cfg.rope_theta),
                norm_eps=cfg.norm_eps, top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk,
                held_first=cfg.moe_held[0], routed_scale=cfg.moe_routed_scale,
                faults=frozenset(faults))


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(7)
    params = jax.jit(lambda k: seed_leaves(init_params(CFG, k), k))(key)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, SEQ), 0, CFG.vocab_size)
    return params, tokens


@pytest.fixture(scope="module")
def conv_layer(seeded):
    return seeded[0]["lead_layers"]["layer1"]


# one compile a program and a reference for the whole file: the tests below
# differ in the leaves or the faults they hand these, not in the program
@functools.lru_cache(maxsize=None)
def program_logits(cfg):
    return jax.jit(lambda p, t: forward(p, t, cfg))


@functools.lru_cache(maxsize=None)
def reference_logits(*faults):
    return jax.jit(lambda p, t: ref.logits(p, t, arch_of(CFG, *faults)))


@pytest.fixture(scope="module")
def sound(seeded):
    """(the program's logits [1, S, V], the reference's (logits [S, V], what it
    saw)) of the first row under the true configuration."""
    params, tokens = seeded
    return program_logits(CFG)(params, tokens[:1]), reference_logits()(params, tokens[0])


@pytest.fixture(scope="module")
def program_step(seeded):
    """((loss, aux), gradients) of the program's loss on both rows."""
    params, tokens = seeded
    return jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=16, return_aux=True),
        has_aux=True))(params)


def plain_mixer(h, w):
    """The mixer's text, position by position in numpy float64: h [S, E]."""
    h, w_in, taps, w_out = (np.asarray(t, np.float64) for t in (
        h, w["w_in"], w["conv"], w["w_out"]))
    b, c, x = (h @ w_in[:, i] for i in range(3))
    u = b * x
    v = np.zeros_like(u)
    for t in range(len(u)):
        for i, tap in enumerate(taps):
            if t - (len(taps) - 1) + i >= 0:
                v[t] += tap * u[t - (len(taps) - 1) + i]
    return (c * v) @ w_out, u, v


def test_the_tree_has_the_kinds_leaves_one_table_and_matching_axes(seeded):
    params, _ = seeded
    full = PRESETS["conv-moe-debug"]
    assert (full.lead_pattern, full.layer_pattern, full.n_periods, full.sconv_taps) == (
        ("sconv", "sconv"), ("attn", "sconv", "sconv", "sconv"), 2, 3)
    assert "lm_head" not in params and "lm_head" not in param_axes(CFG)
    assert jax.tree.structure(jax.eval_shape(lambda: init_params(full, jax.random.PRNGKey(0)))
                              ) == jax.tree.structure(
        param_axes(full), is_leaf=lambda x: isinstance(x, tuple))
    lead, slot = params["lead_layers"]["layer0"], params["layers"]["slot1"]
    e = CFG.hidden
    assert {k: lead[k].shape for k in ("w_in", "conv", "w_out")} == {
        "w_in": (e, 3, e), "conv": (3, e), "w_out": (e, e)}
    assert slot["w_in"].shape == (1, e, 3, e) and "w_gate" in lead and "router" in slot
    assert "wq" not in lead and params["layers"]["slot0"]["q_norm"].shape == (1, CFG.head_dim)
    assert MIXERS["sconv"].save_names == SAVE_NAMES == ("post_attn",)
    # the kind's products are the model's FLOPs; the taps and the gates are not
    assert MIXERS["sconv"].matmul_params(CFG) == 4 * e * e
    assert MIXERS["sconv"].mixing_flops(CFG, SEQ) == 0.0


def test_the_mixer_equals_the_plain_functions_in_value_and_gradient(conv_layer):
    h, g = (jax.random.normal(jax.random.PRNGKey(k), (SEQ, CFG.hidden)) for k in (1, 2))
    positions = jnp.arange(SEQ, dtype=jnp.int32)
    run = lambda h, w: sconv_mixer(h[None], w, config=CFG, positions=positions)[0][0]  # noqa: E731
    want, u, v = plain_mixer(h, conv_layer)
    got, aux = jax.jit(lambda h, w: sconv_mixer(h[None], w, config=CFG, positions=positions))(
        h, conv_layer)
    assert _err(got[0], want) < TIGHT
    # the counter: what the two earlier taps give of the conv's output
    past = v - np.asarray(conv_layer["conv"], np.float64)[2] * u
    assert float(aux["past_share"]) == pytest.approx((past ** 2).sum() / (v ** 2).sum(), rel=1e-4)
    assert 0.4 < float(aux["past_share"]) < 0.9
    # the gradients of the input and of every leaf against the reference's mixer
    pull = lambda fn: jax.jit(lambda h, w: jax.vjp(fn, h, w)[1](g))(h, conv_layer)  # noqa: E731
    got_h, got_w = pull(run)
    want_h, want_w = pull(lambda h, w: ref.conv_mixer(h, w)[0])
    assert _err(got_h, want_h) < TIGHT
    assert max(jax.tree.leaves(jax.tree.map(_err, got_w, want_w))) < TIGHT
    assert _err(ref.conv_mixer(h, conv_layer)[0], want) < TIGHT
    assert float(ref.conv_mixer(h, conv_layer)[1]) == pytest.approx(float(aux["past_share"]),
                                                                    rel=1e-4)


def test_zeros_stand_before_a_row_and_rows_do_not_see_each_other(conv_layer):
    taps = np.asarray(conv_layer["conv"], np.float64)
    b, c, x = (jax.random.normal(jax.random.PRNGKey(k), (2, SEQ, CFG.hidden)) for k in (3, 4, 5))
    got = np.asarray(jax.jit(gated_conv)(b, c, x, conv_layer["conv"])[0], np.float64)
    u, c64 = np.asarray(b, np.float64) * np.asarray(x, np.float64), np.asarray(c, np.float64)
    # the first two positions of a row: nothing before it
    assert np.allclose(got[:, 0], c64[:, 0] * taps[2] * u[:, 0], rtol=1e-5, atol=1e-6)
    assert np.allclose(got[:, 1], c64[:, 1] * (taps[1] * u[:, 0] + taps[2] * u[:, 1]),
                       rtol=1e-5, atol=1e-6)
    assert np.allclose(got[:, 2], c64[:, 2] * (taps[0] * u[:, 0] + taps[1] * u[:, 1]
                                               + taps[2] * u[:, 2]), rtol=1e-5, atol=1e-6)
    # a row alone gives what it gives beside another; the other row's tail
    # does not leak into its first positions
    alone = np.asarray(jax.jit(gated_conv)(b[1:], c[1:], x[1:], conv_layer["conv"])[0])
    assert np.array_equal(alone[0], np.asarray(got[1], alone.dtype))
    # a later position never reaches an earlier one
    moved = x.at[:, 20:].add(1.0)
    again = np.asarray(jax.jit(gated_conv)(b, c, moved, conv_layer["conv"])[0])
    assert np.array_equal(again[:, :20], np.asarray(got[:, :20], again.dtype))
    assert not np.allclose(again[:, 20:23], got[:, 20:23])


def test_the_stack_equals_the_reference_in_logits_loss_and_every_leafs_gradient(
        seeded, sound, program_step):
    params, tokens = seeded
    arch = arch_of(CFG)
    got, (want, seen) = sound
    assert float(jnp.max(ref.position_errors(got[0], want))) < TIGHT
    weight = CFG.moe_aux_weight
    (loss, aux), grads = program_step
    (ref_loss, ref_seen), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, aux_weight=weight, return_seen=True),
        has_aux=True))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert abs(float(aux["load_balance"]) - float(ref_seen["balance"])) < 1e-5
    errs = jax.tree.map(_err, grads, ref_grads)
    assert max(jax.tree.leaves(errs)) < 2e-4, errs
    # no gradient reaches a selection bias
    assert not np.asarray(grads["layers"]["slot0"]["router_bias"]).any()
    assert float(aux["sconv_past_share"]) == pytest.approx(float(ref_seen["past_share"]),
                                                           abs=5e-3)
    assert (np.asarray(aux["rows_per_expert"]) == np.asarray(ref_seen["rows_per_expert"])).all()
    # the reference's own block-at-a-time gradient is the same numbers
    total, by_hand, by_name = ref.loss_and_grads(params, tokens, arch, aux_weight=weight)
    assert abs(total - float(ref_loss)) < 1e-5 and _err(by_hand["logits"], want) < 1e-5
    assert by_hand["past_share"] == pytest.approx(float(ref_seen["past_share"]), abs=1e-6)
    flat = {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_flatten_with_path(ref_grads)[0]}
    assert set(by_name) == set(flat)
    assert max(_err(by_name[k], flat[k]) for k in flat if np.asarray(flat[k]).any()) < 1e-4
    assert all(v.dtype == np.float32 for v in by_name.values())


PROGRAM_FAULTS = {
    "conv_left_out": {}, "taps_reversed": {}, "thirds_x_b_c": {},
    "no_qk_norm": {"head_qk_norm": False}, "softmax_router": {"moe_score": "softmax"},
    "bias_dropped": {"moe_bias_rate": 0.0}}
REFERENCE_FAULTS = ("no_c_gate", "silu_after_conv", "no_rope", "bias_on_gates")


@pytest.mark.parametrize("fault", [*PROGRAM_FAULTS, *REFERENCE_FAULTS])
def test_each_misreading_moves_the_logits(seeded, sound, fault):
    """The runner's controls at test size: a conv left out, its taps reversed,
    the thirds misread, the head norms or the bias left out, a softmax router
    in the program; a gate left out, a silu put in, rope left out or the bias
    on the gates in the reference."""
    params, tokens = seeded
    got, (want, _) = sound
    if fault in REFERENCE_FAULTS:
        want, _ = reference_logits(fault)(params, tokens[0])
    else:
        cfg = dataclasses.replace(CFG, **PROGRAM_FAULTS[fault])
        given = planted(params, fault)
        if fault == "bias_dropped":  # the router then has no such leaf to read
            given = jax.tree.map(lambda a: a, params)
            for slot in given["layers"].values():
                slot.pop("router_bias")
        got = program_logits(cfg)(given, tokens[:1])
    got = got[0]
    # a hundred times what rounding gives and more (the bias on the gates, a
    # seeded +-0.05 on scores near a half, is the weakest: 4e-3)
    assert float(jnp.median(ref.position_errors(got, want))) > 100 * TIGHT, fault


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(seeded):
    """The guide's share test: 8 experts over 4 chips, 2 a chip. Each chip's
    program routes over all 8 and computes its own two's part; the parts,
    summed (nothing is computed alike on every chip: no shared expert), are
    what the uncut reference gives for the whole layer."""
    params, _ = seeded
    held = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    e, x, f = CFG.hidden, CFG.moe_experts, CFG.intermediate
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    draw = lambda k, shape, fan: jax.random.normal(k, shape) * fan ** -0.5  # noqa: E731
    whole = {**held, "w_gate": draw(keys[0], (x, e, f), e), "w_up": draw(keys[1], (x, e, f), e),
             "w_down": draw(keys[2], (x, f, e), f)}
    h = jax.random.normal(keys[3], (SEQ, e))
    kw = dict(top_k=CFG.moe_top_k, norm_topk=CFG.moe_norm_topk)
    want, routing = jax.jit(lambda h, w: ref.expert_layer(h, w, first=0, **kw))(h, whole)
    parts, rows = [], 0
    for first in range(0, x, 2):
        share = {**whole, **{k: whole[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
        y, aux = jax.jit(lambda h, w, first=first: moe_block(
            h[None], w, held=(first, 2), score="sigmoid", **kw))(h, share)
        parts.append(y[0])
        rows += int(aux["rows_held"].sum())
        # the reference's own share is the same part
        own, _ = jax.jit(lambda h, w, first=first: ref.expert_layer(h, w, first=first, **kw))(
            h, share)
        assert _err(y[0], own) < TIGHT
    assert _err(sum(parts), want) < TIGHT
    assert rows == SEQ * CFG.moe_top_k == int(routing["rows"].sum())
    assert max(_err(p, want) for p in parts) > 0.5


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(seeded, program_step):
    params, tokens = seeded
    batch = {"tokens": tokens}
    tied = program_step[1]["embed"]
    apart_cfg = dataclasses.replace(CFG, tie_embeddings=False)
    apart = jax.jit(jax.grad(lambda p: loss_fn(p, batch, apart_cfg, chunk_tokens=16)))(
        {**params, "lm_head": params["embed"].T})
    assert _err(tied, apart["embed"] + apart["lm_head"].T) < 1e-5
    assert _err(apart["lm_head"].T, tied) > 0.1 and _err(apart["embed"], tied) > 0.1
    assert train_flops_per_token(CFG, SEQ) == train_flops_per_token(apart_cfg, SEQ)
