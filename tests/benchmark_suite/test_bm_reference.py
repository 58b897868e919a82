"""The plain reference agrees with the program's forward pass at a tiny
size on the CPU, on seeded weights, and the copied FLOPs arithmetic with
the program's."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops
from benchmark.manifest import HERE
from benchmark.reference import dense_decoder
from benchmark.runners import llama_config
from benchmark.runners.train import LOGIT_RTOL
from ray_tpu.models import forward, init_params, loss_fn
from ray_tpu.models.llama import train_flops_per_token

with open(os.path.join(HERE, "rehearse.json")) as f:
    MODEL = json.load(f)["model"]


@pytest.fixture(scope="module")
def setup():
    # float32 weights and the program's own plain attention: any difference
    # left is the mathematics, so the tolerance can be tight
    cfg = llama_config(MODEL, dtype=jnp.float32, attn_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (48,), 0, MODEL["vocab_size"])
    return cfg, params, tokens


def test_logits_agree(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = forward(params, tokens[None], cfg)[0]
    got = dense_decoder.logits(params, tokens, rope_theta=MODEL["rope_theta"],
                               norm_eps=MODEL["rms_norm_eps"])
    # float32 both sides: 1e-4 at logits of unit scale. A wrong rope
    # convention, a missing GQA repeat or a dropped layer is off by >1e-1.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_loss_agrees(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want = loss_fn(params, {"tokens": tokens[None]}, cfg)
    got = dense_decoder.loss(params, tokens, rope_theta=MODEL["rope_theta"],
                             norm_eps=MODEL["rms_norm_eps"])
    assert float(got) == pytest.approx(float(want), abs=1e-4)


@pytest.fixture(scope="module")
def bf16():
    """The train runner's own comparison at a small size: the program in
    bf16 (as the cells run it) against the float32 reference on the same
    bf16-rounded weights. Six layers: with two, the last is half the model."""
    model = dict(MODEL, num_hidden_layers=6)
    cfg = llama_config(model)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, model["vocab_size"])
    want = [dense_decoder.logits(params, row, rope_theta=model["rope_theta"],
                                 norm_eps=model["rms_norm_eps"]) for row in tokens]

    def errors(p, c):
        got = forward(p, tokens, c)
        return float(max(dense_decoder.position_errors(g, w).max()
                         for g, w in zip(got, want)))

    return cfg, params, errors


def test_the_program_in_bf16_stays_inside_the_runners_tolerance(bf16):
    cfg, params, errors = bf16
    assert errors(params, cfg) <= LOGIT_RTOL / 2


def test_a_dropped_layer_fails(bf16):
    cfg, params, errors = bf16
    short = dict(params, layers=jax.tree.map(lambda a: a[:-1], params["layers"]))
    assert errors(short, dataclasses.replace(cfg, n_layers=cfg.n_layers - 1)) > 2 * LOGIT_RTOL


def test_weights_rounded_to_fp8_fail(bf16):
    cfg, params, errors = bf16
    fp8 = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    assert errors(fp8, cfg) > 2 * LOGIT_RTOL


def test_one_bad_position_fails_though_the_mean_loss_hardly_moves(bf16):
    want = jax.random.normal(jax.random.PRNGKey(0), (64, 512))
    got = want.at[17].multiply(1.3)
    err = dense_decoder.position_errors(got, want)
    assert float(err[17]) == pytest.approx(0.3, rel=1e-3) and float(err.max()) > LOGIT_RTOL
    assert float(jnp.delete(err, 17).max()) == 0.0


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mistral-7b-v0.3"])
def test_copied_flops_arithmetic_matches_the_program(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert flops.train_flops_per_token(cfg["model"], 4096) == pytest.approx(
        train_flops_per_token(llama_config(cfg["model"]), 4096))
    assert flops.param_count(cfg["model"]) == cfg["parameters"]


def test_an_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
