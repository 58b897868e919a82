"""The Mamba-2 mixer, the ten-position period, the tied table and the softmax
constant (``granite-hybrid-debug``, Pallas interpreted on the CPU) against the
plain reference ``benchmark/reference/ssm_decoder.py`` on seeded float32
weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssm_decoder as ref
from benchmark.runners.train_hybrid import seed_norms
from ray_tpu.models import PRESETS, init_params, loss_fn, param_axes
from ray_tpu.models.llama import forward, train_flops_per_token
from ray_tpu.models.mamba2 import mamba2_mixer
from ray_tpu.ops.ssd import ssd_scan

TEN = dataclasses.replace(PRESETS["granite-hybrid-debug"], dtype=jnp.float32,
                          remat_policy="attn")
# the same widths at three positions, for what needs a gradient: the CPU
# compiles every interpreted kernel call
CFG = dataclasses.replace(TEN, n_layers=3, layer_pattern=("mamba2", "gqa", "mamba2"))
SEQ = 40   # two chunks of 16 and a ragged third
# float32 program against the float32 reference: rounding alone
TIGHT = 2e-5


def arch_of(cfg, **over) -> dict:
    m, g = cfg.mamba2, cfg.gqa
    return dict(kinds={"mamba2": dict(heads=m.heads, head_dim=m.head_dim, state=m.state,
                                      groups=m.groups, conv=m.conv, chunk=m.chunk),
                       "gqa": dict(heads=g.heads, kv_heads=g.kv_heads, head_dim=g.head_dim,
                                   rope_theta=0.0, softmax_scale=g.softmax_scale)},
                pattern=cfg.layer_pattern, lead_pattern=(), norm_eps=cfg.norm_eps,
                embed_scale=cfg.embed_scale, residual_scale=cfg.residual_scale,
                logit_scale=cfg.logit_scale, **over)


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _seeded(cfg):
    key = jax.random.PRNGKey(7)
    params = jax.jit(lambda k: seed_norms(init_params(cfg, k), k))(key)
    # the skips and the steps' biases away from their start, so that one left out shows
    move = lambda a, k: a + jax.random.uniform(jax.random.fold_in(key, k), a.shape,  # noqa: E731
                                               minval=-0.5, maxval=0.5)
    layers = {slot: {**layer, **({"d_skip": move(layer["d_skip"], 1)} if "d_skip" in layer
                                 else {})} for slot, layer in params["layers"].items()}
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, SEQ), 0, cfg.vocab_size)
    return {**params, "layers": layers}, tokens


@pytest.fixture(scope="module")
def seeded():
    return _seeded(CFG)


def test_the_tree_has_one_table_and_the_axes_match(seeded):
    params, _ = seeded
    assert "lm_head" not in params and "lm_head" not in param_axes(TEN)
    assert jax.tree.structure(jax.eval_shape(lambda: init_params(TEN, jax.random.PRNGKey(0)))
                              ) == jax.tree.structure(
        param_axes(TEN), is_leaf=lambda x: isinstance(x, tuple))
    assert TEN.n_periods == 1 and len(TEN.layer_pattern) == 10
    layer = params["layers"]["slot0"]
    assert {k: layer[k].dtype for k in ("dt_bias", "a_log", "d_skip")} == {
        k: jnp.float32 for k in ("dt_bias", "a_log", "d_skip")}
    # the decays span the published start: head h forgets at rate h + 1
    assert np.allclose(np.exp(layer["a_log"][0]), np.arange(1, 5))


@pytest.mark.parametrize("fault,limit", [(None, TIGHT), ("gate_after_norm", None),
                                         ("bf16_state", None)])
def test_the_mixer_equals_the_reference_and_a_fault_does_not(seeded, fault, limit):
    params, _ = seeded
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot2"])
    h, g = (jax.random.normal(jax.random.PRNGKey(k), (SEQ, CFG.hidden)) for k in (1, 2))
    positions = jnp.arange(SEQ, dtype=jnp.int32)
    scan = None
    if fault == "bf16_state":
        scan = lambda *a: ssd_scan(*a, state_dtype=jnp.bfloat16)  # noqa: E731
    spec = arch_of(CFG)["kinds"]["mamba2"]

    def both(fn):
        y, pull = jax.vjp(fn, h)
        return y, pull(g)[0]

    got = jax.jit(lambda: both(lambda h: mamba2_mixer(
        h[None], layer, config=CFG, positions=positions, scan=scan)[0][0]))()
    want = jax.jit(lambda: both(lambda h: ref.mamba_mixer(
        h, layer, spec, CFG.norm_eps, gate_inside=fault != "gate_after_norm")))()
    errs = (_err(got[0], want[0]), _err(got[1], want[1]))
    if fault is None:
        assert max(errs) < limit, errs
    else:   # ten times the limit and more (a bf16 state over 40 positions: 4e-4)
        assert min(errs) > 10 * TIGHT, errs


def test_the_ten_position_period_equals_the_reference_in_logits():
    params, tokens = _seeded(TEN)
    got = jax.jit(lambda p, t: forward(p, t, TEN))(params, tokens[:1])[0]
    want = jax.jit(lambda p, t: ref.logits(p, t, arch_of(TEN)))(params, tokens[0])
    assert float(jnp.max(ref.position_errors(got, want))) < TIGHT


def test_a_period_equals_the_reference_in_logits_loss_and_gradients(seeded):
    params, tokens = seeded
    arch = arch_of(CFG)
    want = jax.jit(lambda p, t: ref.logits(p, t, arch))(params, tokens[0])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=16, return_aux=True),
        has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, tokens, arch)))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    worst = max(jax.tree.leaves(jax.tree.map(_err, grads, ref_grads)))
    assert worst < 2e-4, jax.tree.map(_err, grads, ref_grads)
    assert 0.5 < float(aux["ssm_decay_mean"]) < 1.0
    # the reference's own block-at-a-time gradient is the same numbers
    ce, seen, by_name = ref.loss_and_grads(params, tokens, arch)
    assert abs(ce - float(ref_loss)) < 1e-5 and _err(seen["logits"], want) < 1e-5
    flat = {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_flatten_with_path(ref_grads)[0]}
    assert set(by_name) == set(flat)
    assert max(_err(by_name[k], flat[k]) for k in flat) < 1e-4


@pytest.mark.parametrize("change,field", [
    ({"residual_scale": 1.0}, None), ({"logit_scale": 1.0}, None), ({"embed_scale": 1.0}, None),
    ({}, "softmax_scale")], ids=["residual_1", "logits_1", "embed_1", "sqrt_scale"])
def test_each_multiplier_and_the_softmax_constant_matter(seeded, change, field):
    params, tokens = seeded
    cfg = dataclasses.replace(CFG, **change)
    if field:
        cfg = dataclasses.replace(cfg, gqa=dataclasses.replace(cfg.gqa, softmax_scale=None))
    want = jax.jit(lambda p, t: ref.logits(p, t, arch_of(CFG)))(params, tokens[0])
    got = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens[:1])[0]
    assert float(jnp.max(ref.position_errors(got, want))) > 1e-2


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(seeded):
    params, tokens = seeded
    batch = {"tokens": tokens}
    tied = jax.jit(jax.grad(lambda p: loss_fn(p, batch, CFG, chunk_tokens=16)))(params)["embed"]
    apart_cfg = dataclasses.replace(CFG, tie_embeddings=False)
    apart = jax.jit(jax.grad(lambda p: loss_fn(p, batch, apart_cfg, chunk_tokens=16)))(
        {**params, "lm_head": params["embed"].T})
    assert _err(tied, apart["embed"] + apart["lm_head"].T) < 1e-6
    assert _err(apart["lm_head"].T, tied) > 0.1 and _err(apart["embed"], tied) > 0.1
    # the head's product counts once in the model's FLOPs, tied or not
    assert train_flops_per_token(CFG, SEQ) == train_flops_per_token(apart_cfg, SEQ)


def test_more_than_one_device_is_refused():
    class Mesh:
        size = 2

    with pytest.raises(NotImplementedError, match="one device"):
        mamba2_mixer(jnp.zeros((1, 16, CFG.hidden)), {}, config=CFG,
                     positions=jnp.arange(16), mesh=Mesh())
