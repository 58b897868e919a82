"""The latent-attention decoder that attends every causal key
(``models/mla.py``'s kind ``mla_full``: YaRN with its factor on the softmax
scale), a leading dense layer and sigmoid-routed experts with a selection
bias under a routed scale (``models/moe.py``) against its plain reference
(``benchmark/reference/latent_decoder.py``) on seeded weights, in float32 on
the CPU with the Pallas kernels interpreted, at a size where YaRN's factor
moves a frequency and the held experts are a sixth of the router's."""

import dataclasses
import zlib
import math

import jax
import jax.numpy as jnp
import jaxpr_walk
import numpy as np
import optax
import pytest

from benchmark.reference import latent_decoder as ref
from ray_tpu.models import PRESETS, init_params, loss_fn, update_buffers
from ray_tpu.models.mla import LatentAttention, LatentAttentionYarn, mla_mixer
from ray_tpu.models.moe import moe_block
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.rope import yarn_frequencies

CFG = dataclasses.replace(PRESETS["latent-full-debug"], dtype=jnp.float32,
                          remat_policy="attn")
SEQ = 32


def spec_of(a: LatentAttention) -> dict:
    return dataclasses.asdict(a)


def arch_of(c) -> dict:
    return {"spec": spec_of(c.mla_full), "lead_layers": len(c.lead_pattern),
            "norm_eps": c.norm_eps, "top_k": c.moe_top_k, "norm_topk": c.moe_norm_topk,
            "held_first": c.moe_held[0] if c.moe_held else 0,
            "routed_scale": c.moe_routed_scale}


ARCH = arch_of(CFG)


@pytest.fixture(scope="module")
def params():
    p = jax.jit(lambda key: init_params(CFG, key))(jax.random.PRNGKey(0))

    def move(path, leaf):  # norms off 1 and biases off 0: one left out must show
        name = str(getattr(path[-1], "key", ""))
        # crc32 and not ``hash``, which differs from one process to the next
        key = jax.random.fold_in(jax.random.PRNGKey(1),
                                 zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm"):
            return leaf + jax.random.uniform(key, leaf.shape, minval=-0.5, maxval=0.5)
        if name == "router_bias":
            return leaf + jax.random.uniform(key, leaf.shape, minval=-0.05, maxval=0.05)
        return leaf

    return jax.tree_util.tree_map_with_path(move, p)


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(5), (SEQ, CFG.hidden))


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def layer_of(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def test_yarn_at_the_published_numbers_matches_values_worked_by_hand():
    # Kimi-K2: 64 rotated features, theta 50,000, factor 32 over 4,096 positions,
    # beta_fast = beta_slow = 1, mscale = mscale_all_dim = 1
    d, theta, factor, length = 64, 50_000.0, 32.0, 4096
    dim = d * math.log(length / (2 * math.pi)) / (2 * math.log(theta))
    assert abs(dim - 19.16) < 0.01 and (math.floor(dim), math.ceil(dim)) == (19, 20)
    got = np.asarray(yarn_frequencies(d, theta=theta, factor=factor, original_length=length,
                                      beta_fast=1.0, beta_slow=1.0))
    assert got.shape == (32,) and got.dtype == np.float32
    f = lambda i: theta ** (-2 * i / d)  # noqa: E731
    # the pair that turns once in 4,096 positions lies between 19 and 20: pairs
    # 0-19 keep their frequency, 20-31 are slowed 32-fold, none is blended
    for i in range(32):
        want = f(i) if i <= 19 else f(i) / 32
        assert abs(got[i] - want) <= 2e-6 * want, (i, got[i], want)
    assert abs(f(20) - 1.1565e-3) < 1e-7 and abs(got[20] - 3.614e-5) < 1e-8
    # at the 2,048th position pair 20 has turned 0.07 rad and not 2.4
    assert abs(2047 * got[20] - 0.074) < 1e-3 and abs(2047 * f(20) - 2.37) < 1e-2
    # the reference writes the same equations out for itself
    assert np.allclose(got, np.asarray(ref.yarn_inv_freq(d, theta, factor, length, 1.0, 1.0)),
                       rtol=2e-6, atol=0)
    # its factors: cos and sin by mscale(32, 1) / mscale(32, 1) = 1, the softmax
    # scale by mscale(32, 1)^2; the grouped-query form's factor is its root
    on_cos, on_scale = ref.yarn_factors(32.0, 1.0, 1.0)
    assert on_cos == 1.0 and abs(on_scale - 1.81326) < 1e-5
    assert abs(math.sqrt(on_scale) - 1.34657) < 1e-5
    assert ref.yarn_factors(32.0, 1.0, 0.0) == (0.1 * math.log(32) + 1, 1.0)
    # the preset's: 2 pairs at theta 100 over 16 positions, the second slowed 8-fold
    a = CFG.mla_full
    small = yarn_frequencies(a.rope_dim, theta=a.rope_theta, factor=a.yarn.factor,
                             original_length=a.yarn.original_length,
                             beta_fast=a.yarn.beta_fast, beta_slow=a.yarn.beta_slow)
    assert np.allclose(small, [1.0, 0.1 / 8], rtol=1e-6)
    assert a.softmax_factor == (0.1 * math.log(8.0) + 1.0) ** 2 and a.yarn.attention_factor == 1.0


@pytest.mark.parametrize("heads,d,dv", [(4, 24, 16), (2, 48, 32)], ids=["24_16", "48_32"])
def test_the_plain_kernels_at_unequal_widths_match_mha_reference(heads, d, dv):
    """``flash_*`` at a 3 : 2 query/value ratio, query heads = key heads, under
    a softmax scale that is not d^-1/2: values and all three gradients."""
    key = jax.random.PRNGKey(3)
    b, s, scale = 1, 128, 1.7 / math.sqrt(d)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, heads, s, d)) for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, heads, s, dv))
    got = jax.jit(lambda *x: flash_attention(*x, sm_scale=scale, block_q=32, block_k=32))
    want = jax.jit(lambda *x: mha_reference(*x, sm_scale=scale))
    assert got(q, k, v).shape == (b, heads, s, dv)
    assert rel(got(q, k, v), want(q, k, v)) < 1e-5
    g = jax.jit(jax.grad(lambda *x: jnp.sum(got(*x) ** 2), (0, 1, 2)))(q, k, v)
    w = jax.jit(jax.grad(lambda *x: jnp.sum(want(*x) ** 2), (0, 1, 2)))(q, k, v)
    assert max(rel(a, b_) for a, b_ in zip(g, w)) < 1e-5


def _yarn(a, **kw):
    return dataclasses.replace(a.yarn, **kw)


FAULTS = {
    None: lambda a: a,
    "plain_rope": lambda a: dataclasses.replace(a, yarn=None),
    "no_mscale": lambda a: dataclasses.replace(a, softmax_factor=1.0),
    "yarn_on_cos": lambda a: dataclasses.replace(
        a, softmax_factor=1.0, yarn=_yarn(a, attention_factor=math.sqrt(a.softmax_factor))),
    "window_8": lambda a: dataclasses.replace(a, window=8),
    "rescaled_latents": lambda a: dataclasses.replace(a, rescale=True),
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=lambda f: f or "sound")
def test_the_mixer_matches_the_reference_and_a_planted_fault_does_not(params, hidden, fault):
    layer = layer_of(params)
    want = jax.jit(lambda h, w: ref.mla_mixer(h, w, ARCH["spec"], CFG.norm_eps))(hidden, layer)
    spec = FAULTS[fault](CFG.mla_full)
    got, aux = jax.jit(lambda h, w: mla_mixer(
        h[None], w, spec, config=CFG, positions=jnp.arange(SEQ, dtype=jnp.int32)))(hidden, layer)
    assert aux == {}
    if fault is None:
        assert rel(got[0], want) < 2e-5
    else:
        assert rel(got[0], want) > 0.02, fault


def test_fp8_weights_read_far_over_a_sound_layers_error(params, hidden):
    layer = layer_of(params)
    fp8 = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), layer)
    want = jax.jit(lambda h, w: ref.mla_mixer(h, w, ARCH["spec"], CFG.norm_eps))(hidden, layer)
    got, _ = jax.jit(lambda h, w: mla_mixer(
        h[None], w, CFG.mla_full, config=CFG, positions=jnp.arange(SEQ)))(hidden, fp8)
    assert rel(got[0], want) > 0.02
    kw = dict(top_k=3, norm_topk=True, held=CFG.moe_held, score="sigmoid", routed_scale=2.827)
    want, _ = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=3, norm_topk=True, first=0, scale=2.827))(hidden, layer)
    got, _ = jax.jit(lambda h, w: moe_block(h[None], w, **kw))(hidden, fp8)
    assert rel(got[0], want) > 0.02


@pytest.mark.parametrize("fault", [None, "no_scale", "top_2", "not_renormalised", "bias_ignored",
                                   "softmax_router", "sigmoid_over_the_held"])
def test_the_expert_layer_matches_the_reference_and_a_planted_fault_does_not(
        params, hidden, fault):
    layer = layer_of(params)
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=3, norm_topk=True, first=0, scale=2.827))(hidden, layer)
    kw = dict(top_k=3, norm_topk=True, held=CFG.moe_held, score="sigmoid", routed_scale=2.827)
    given = layer
    if fault == "no_scale":
        kw["routed_scale"] = 1.0
    elif fault == "top_2":
        kw["top_k"] = 2
    elif fault == "not_renormalised":
        kw["norm_topk"] = False
    elif fault == "bias_ignored":
        given = {k: v for k, v in layer.items() if k != "router_bias"}
    elif fault == "softmax_router":
        kw["score"] = "softmax"
    elif fault == "sigmoid_over_the_held":
        given = {**layer, "router": layer["router"][:, :2], "router_bias": layer["router_bias"][:2]}
        kw.update(held=None, top_k=2)
    got, aux = jax.jit(lambda h, w: moe_block(h[None], w, **kw))(hidden, given)
    if fault is None:
        assert rel(got[0], want) < 2e-5
        assert int(aux["dropped"]) == 0 and int(aux["rows"].sum()) == SEQ * 3
        assert np.array_equal(np.asarray(aux["rows"]), np.asarray(routing["rows"]))
        assert abs(float(aux["held_share"]) - float(routing["rows"][:2].sum()) / (SEQ * 3)) < 1e-6
    else:
        assert rel(got[0], want) > 0.05, fault


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(hidden):
    """Six chips hold two experts each of twelve: what each share's held
    experts add, with the shared expert counted once, is the uncut
    reference's layer; the program's share equals the reference's."""
    whole_cfg = dataclasses.replace(CFG, moe_held=None)
    whole = jax.tree.map(lambda a: a[0], jax.jit(lambda key: init_params(whole_cfg, key)[
        "layers"])(jax.random.PRNGKey(7)))
    whole["router_bias"] = jax.random.uniform(jax.random.PRNGKey(8), (12,), minval=-0.05,
                                              maxval=0.05)
    kw = dict(top_k=3, norm_topk=True, scale=2.827)
    layer_of_share = jax.jit(lambda h, w, first: ref.expert_layer(h, w, first=first, **kw)[0],
                             static_argnums=2)
    want = layer_of_share(hidden, whole, 0)
    experts = ("w_gate", "w_up", "w_down")
    none_held = {**whole, **{k: whole[k][:0] for k in experts}}
    total = layer_of_share(hidden, none_held, 0)          # the shared expert, once
    shared = total
    for first in range(0, 12, 2):
        share = {**whole, **{k: whole[k][first:first + 2] for k in experts}}
        part = layer_of_share(hidden, share, first)
        total = total + part - shared
        if first == 6:  # one share through the program too: its range starts past 0
            got, _ = jax.jit(lambda h, w: moe_block(
                h[None], w, top_k=3, norm_topk=True, held=(first, 2), score="sigmoid",
                routed_scale=2.827))(hidden, share)
            assert rel(got[0], part) < 2e-5
    assert rel(total, want) < 1e-5


@pytest.mark.parametrize("skew", [0.0, 6.0], ids=["even", "skewed"])
def test_a_held_range_of_several_chunks_drops_no_row_and_has_the_references_gradients(
        params, skew, monkeypatch):
    """2 of 12 experts held, top-3 of 96 tokens, the compact path compiled for
    32 rows (a capacity of half the even share, for the test). A router pushed
    towards the held pair gives each of them all 96 tokens: the first takes the
    compact path's 32 rows and two chunks past them, the second three chunks,
    and values and gradients are still the reference's."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "HELD_CAPACITY", 0.5)
    assert moe._held_capacity(96 * 3, (0, 2), 12) == 32
    hidden = jax.random.normal(jax.random.PRNGKey(9), (96, CFG.hidden))
    layer = layer_of(params)
    layer = {**layer, "router": layer["router"].at[:, :2].add(
        skew * jnp.sign(hidden.mean(0))[:, None])}
    h = hidden + (1.0 if skew else 0.0) * jnp.sign(hidden.mean(0))
    kw = dict(top_k=3, norm_topk=True, held=(0, 2), score="sigmoid", routed_scale=2.827)
    ref_kw = dict(top_k=3, norm_topk=True, first=0, scale=2.827)
    got, aux = jax.jit(lambda h, w: moe_block(h[None], w, **kw))(h, layer)
    want, _ = jax.jit(lambda h, w: ref.expert_layer(h, w, **ref_kw))(h, layer)
    assert rel(got[0], want) < 2e-5 and int(aux["dropped"]) == 0
    held_rows = np.asarray(aux["rows_held"]).tolist()
    assert (sum(held_rows) > 5 * 32) if skew else (32 < sum(held_rows) < 96), held_rows
    grad = jax.jit(jax.grad(lambda w, h: moe_block(h[None], w, **kw)[0].sum(), (0, 1)))(layer, h)
    want_grad = jax.jit(jax.grad(lambda w, h: ref.expert_layer(h, w, **ref_kw)[0].sum(), (0, 1)))(
        layer, h)
    assert rel(grad[1], want_grad[1]) < 1e-4
    for name in ("w_gate", "w_up", "w_down", "router", "w_shared_down"):
        assert rel(grad[0][name], want_grad[0][name]) < 1e-4, name


@pytest.fixture(scope="module")
def program_step():
    # every program under one ``jit``: op by op, the interpreted kernels take minutes
    return jax.jit(jax.value_and_grad(
        lambda p, rows: loss_fn(p, {"tokens": rows}, CFG, chunk_tokens=16, return_aux=True),
        has_aux=True))


@pytest.fixture(scope="module")
def reference_step(params, rows):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, rows, ARCH, aux_weight=CFG.moe_aux_weight, return_seen=True),
        has_aux=True))(params)


def test_loss_counters_and_every_gradient_match_the_reference(
        params, rows, program_step, reference_step):
    (loss, aux), grads = program_step(params, rows)
    (want_loss, seen), want_grads = reference_step
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(aux["load_balance"]) - float(seen["balance"])) < 1e-5
    assert np.array_equal(np.asarray(aux["rows_per_expert"]), np.asarray(seen["rows_per_expert"]))
    assert np.allclose(np.asarray(aux["held_share"]),
                       np.asarray(seen["rows_per_expert"])[:, :2].sum(-1) / (2 * SEQ * 3))
    assert int(aux["rows_dropped"]) == 0
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path, g in flat.items():
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):  # no gradient moves it
            assert not np.asarray(g).any() and not np.asarray(want_flat[path]).any()
        else:
            assert rel(g, want_flat[path]) < 2e-4, name
    # the same loss and gradients, a block at a time and by hand
    by_hand_loss, by_hand_seen, by_hand = ref.loss_and_grads(
        params, rows, ARCH, aux_weight=CFG.moe_aux_weight)
    assert abs(by_hand_loss - float(want_loss)) < 1e-5
    assert abs(by_hand_seen["balance"] - float(seen["balance"])) < 1e-6
    assert np.allclose(by_hand_seen["logits"], np.asarray(seen["logits"]), atol=1e-5)
    assert np.allclose(by_hand_seen["biased"], np.asarray(seen["biased"]), atol=1e-6)
    assert np.array_equal(np.asarray(by_hand_seen["rows_per_expert"]),
                          np.asarray(seen["rows_per_expert"]))
    assert {jax.tree_util.keystr(p) for p in want_flat} == set(by_hand)
    for path, g in want_flat.items():
        name = jax.tree_util.keystr(path)
        if not name.endswith("['router_bias']"):
            assert rel(by_hand[name], g) < 1e-5, name
    # the bias's own step, from the step's own counts
    stepped = update_buffers(params, aux, CFG)["layers"]["router_bias"]
    want_bias = ref.bias_after(params["layers"]["router_bias"], seen["rows_per_expert"], 0.001)
    assert np.allclose(np.asarray(stepped), np.asarray(want_bias), atol=1e-7)


ADAFACTOR = optax.adafactor(1e-3)  # one object: ``step_errors`` compiles a leaf's readings once


@pytest.mark.parametrize("control", [None, "half_batch", "unchanged_state"])
def test_the_benchmarks_step_comparison_reads_0_on_the_programs_step_and_1_on_none(
        params, rows, program_step, reference_step, control):
    """``train_swa.step_errors``, which the new runner imports, on this model's
    step: the two controls of the runner that leave the program as it is."""
    from benchmark.runners import train_mla

    opt = ADAFACTOR
    given = rows
    if control == "half_batch":
        half = rows.reshape(-1)[:rows.size // 2]
        given = jnp.concatenate([half, half]).reshape(rows.shape)
    _, grads = program_step(params, given)
    updates, state = jax.jit(opt.update)(grads, opt.init(params), params)
    after = optax.apply_updates(params, updates)
    if control == "unchanged_state":
        after, state = params, opt.init(params)
    want_grads = {jax.tree_util.keystr(path): g for path, g in
                  jax.tree_util.tree_flatten_with_path(reference_step[1])[0]}
    e = train_mla.step_errors(opt, params, after, state, params, want_grads)
    assert e["leaves_judged"] >= 15
    readings = (e["update"]["worst"], e["update"]["median"], e["grad_stats"]["worst"])
    if control is None:
        assert max(readings) < 1e-3, e  # ``update_rounded`` counts a last-place flip whole
    elif control == "unchanged_state":
        assert readings[:2] == (1.0, 1.0) and abs(readings[2] - 1.0) < 1e-6
    else:
        assert min(readings) > 2 * train_mla.UPDATE_ALONG_ATOL
        assert readings[2] > train_mla.GRAD_STATS_RTOL


def test_remat_attn_runs_each_attention_forward_once_and_the_kind_counts_the_whole_row():
    from ray_tpu.models.llama import MIXERS, train_flops_per_token

    c = CFG
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16)))(shapes))
    # the leading layer, and the scanned period's one body
    assert text.count("name=flash_fwd") == 2 and text.count("name=flash_bwd_dq") == 2
    assert text.count("name=flash_bwd_dkdv") == 2 and "attn_win" not in text
    a = c.mla_full
    assert MIXERS["mla_full"].mixing_flops(c, SEQ) == \
        2.0 * a.heads * (a.qk_dim + a.v_dim) * (SEQ + 1) / 2
    assert MIXERS["mla_full"].matmul_params(c) == (
        64 * 32 + 32 * 4 * 12 + 64 * 20 + 16 * 4 * 14 + 4 * 6 * 64)
    assert train_flops_per_token(c, SEQ) > 0


@pytest.mark.parametrize("preset,field,kernel,blocks", [
    ("latent-sparse-debug", "mla", "attn_sel_fwd", 1024),
    ("latent-sparse-debug", "mla_window", "attn_win_fwd", 512),
    ("latent-full-debug", "mla_full", "flash_fwd", 1024)])
def test_the_new_fields_leave_the_older_kinds_as_they_were(preset, field, kernel, blocks):
    """A spec without the new fields is one under YaRN that names their defaults:
    the same jaxpr. Its kernel is called under ``qk_dim^-1/2`` alone (no factor
    multiplies the scale of a kind without YaRN), a window layer's at the
    window's 512 x 512 blocks and a layer with neither window nor indexer at
    the plain kernels' own."""
    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32)
    a = getattr(c, field)
    s = 2048  # past the plain kernels' block, so that a kind's own block shows
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), (
        shapes["layers"]["slot0" if field == "mla" else "slot1"]
        if preset == "latent-sparse-debug" else shapes["layers"]))
    h = jax.ShapeDtypeStruct((1, s, c.hidden), jnp.float32)
    run = lambda spec: jax.make_jaxpr(lambda h, w: mla_mixer(  # noqa: E731
        h, w, spec, config=c, positions=jnp.arange(s, dtype=jnp.int32)))(h, layer)
    jaxpr = run(a)
    if a.yarn is None:  # the accepted tests hold the older specs to their thirteen fields
        assert type(a) is LatentAttention and len(dataclasses.astuple(a)) == 13
        named = LatentAttentionYarn(**dataclasses.asdict(a), yarn=None, softmax_factor=1.0)
        assert str(run(named)) == str(jaxpr)
    calls = [e for e in jaxpr_walk.equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call" and str(e.params["name"]) == kernel]
    assert len(calls) == 1
    text = str(calls[0])
    # the scale is a constant of the kernel's body, the blocks the shapes of its refs
    want_scale = a.qk_dim ** -0.5 * (1.0 if a.yarn is None else a.softmax_factor)
    assert f"{float(np.float32(want_scale))!r}:f32[]" in text
    plain = float(np.float32(a.qk_dim ** -0.5))
    assert (f"{plain!r}:f32[]" in text) == (a.yarn is None)
    assert f"Ref{{f32[1,1,{blocks},{a.qk_dim}]}}" in text
