"""The routed-decoder configuration (OLMoE-1B-7B-0125), its FLOP count, its
plain reference's own invariances, and the runner's tolerances against the
mutations they are meant to catch, at the rehearsal size on the CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_moe
from benchmark.manifest import HERE, Manifest
from benchmark.reference import moe_decoder
from benchmark.runners import RunFailure, train_moe

CELL = "olmoe-1b-7b-0125.train-4k-moe"
# the row of the model-configs guide's catalog (architectures.jsonl), whose
# source_url is the configuration's source
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model with seeded bf16 weights, one check row and one
    layer-check input, as the runner makes them."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-moe.json")) as f:
        doc = json.load(f)
    cfg = train_moe.model_config(doc["model"], doc["train"], remat_policy="attn")  # bf16
    params = train_moe.seed_qk_norms(init_params(cfg, jax.random.PRNGKey(3)), jax.random.PRNGKey(3))
    rows = jax.random.randint(jax.random.PRNGKey(4), (1, 64), 0, cfg.vocab_size)
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden), cfg.dtype)
    return doc, cfg, params, rows, h


def test_the_configuration_keeps_every_published_number_but_the_depth(cell):
    model, doc = cell.config["model"], Manifest().doc
    assert cell.config["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key in cell.config["reduced"]:
            assert model[key] < value
        else:
            assert model[key] == value and type(model[key]) is type(value), key
    assert set(model) - set(CATALOG) == {"head_dim"}
    # the same keys stand at the top level of the file, where the contract
    # compares a catalogued model's numbers
    assert {k: cell.config[k] for k in CATALOG} == {k: model[k] for k in CATALOG}
    assert model["head_dim"] == model["hidden_size"] // model["num_attention_heads"]
    entry = next(c for c in doc["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cell.config["source"] and entry["reduced"] == ["num_hidden_layers"]
    for key in ("source", "assumed", "deployment", "parameters", "reduced_why"):
        assert cell.config[key], key
    assert cell.chips == 1 and cell.traffic["runner"] == "train_moe"
    assert len(doc["workloads"]) == 3 and sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    # one layer: q, k, v, o projections; the router; 8 experts x 3 matrices
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    by_hand = 6 * (model["num_hidden_layers"] * layer + 2048 * 50304) \
        + 6 * model["num_hidden_layers"] * 16 * 128 * seq
    assert flops_moe.train_flops_per_token(model, seq) == by_hand
    cfg = train_moe.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == by_hand
    # the published depth: 2.89 GFLOP a token at 5 layers, as ISSUE 26 counts
    assert flops_moe.train_flops_per_token({**model, "num_hidden_layers": 5}, seq) \
        == pytest.approx(2.887e9, rel=1e-3)
    assert flops_moe.grouped_matmul_flops(model, 4 * 4096) == 2 * 131072 * 2048 * 1024
    assert flops_moe.param_count(model) == cell.config["parameters"]
    assert flops_moe.param_count({**model, "num_hidden_layers": 16}) \
        == cell.config["parameters_published"] == 6_919_161_856


def test_the_counts_are_the_programs_own(tiny):
    """``param_count`` against the leaves ``init_params`` makes, and one
    grouped-matmul call's FLOPs against what the kernel records of itself."""
    from ray_tpu.ops import trace_log

    doc, cfg, params, rows, h = tiny
    assert flops_moe.param_count(doc["model"]) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    train_moe.layer_errors(h, *[(jax.tree.map(lambda a: a[0], params["layers"]), 2, False)] * 2)
    assert trace_log.kernel_costs()["moe_gmm"]["flops"] \
        == flops_moe.grouped_matmul_flops(doc["model"], h.shape[0])


def test_permuting_the_experts_with_their_router_columns_changes_nothing(tiny):
    doc, cfg, params, rows, h = tiny
    arch = train_moe.reference_arch(doc["model"])
    perm = jnp.asarray([2, 0, 3, 1])
    layers = dict(params["layers"])
    layers["router"] = layers["router"][:, :, perm]
    for name in ("w_gate", "w_up", "w_down"):
        layers[name] = layers[name][:, perm]
    want, _ = moe_decoder.logits(params, rows[0], **arch)
    got, _ = moe_decoder.logits({**params, "layers": layers}, rows[0], **arch)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_references_aux_terms_by_hand():
    """Two tokens, three experts, top-1: rows (2, 0, 0), mean probabilities
    (0.6, 0.3, 0.1): load balance 3 * (1 * 0.6) = 1.8; z = mean(lse^2)."""
    probs = jnp.asarray([[[0.7, 0.2, 0.1], [0.5, 0.4, 0.1]]])
    routing = {"probs": probs, "chosen": jnp.asarray([[[0], [0]]]),
               "lse": jnp.asarray([[1.0, 3.0]])}
    balance, z = moe_decoder.aux_losses([routing])
    assert float(balance) == pytest.approx(1.8) and float(z) == pytest.approx(5.0)


def test_near_ties_are_flagged_by_relative_gap():
    probs = jnp.asarray([[0.4, 0.3, 0.2999, 0.0001], [0.4, 0.3, 0.2, 0.1]])
    assert train_moe.near_ties(probs, 2, 1e-3).tolist() == [True, False]
    assert train_moe.near_ties(probs, 1, 1e-3).tolist() == [False, False]


def test_the_runner_refuses_a_model_type_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model of type"):
        train_moe.model_config({**cell.config["model"], "model_type": "mixtral"},
                               cell.config["train"])


def _fp8(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.dtype == jnp.bfloat16 else a,
        tree)


def _neighbour(weights, axis):
    return {**weights, **{k: jnp.roll(weights[k], 1, axis=axis)
                          for k in ("w_gate", "w_up", "w_down")}}


LAYER_MUTATIONS = {
    "none": lambda w: (w, 2, False),
    "top-1 for top-2": lambda w: (w, 1, False),
    "renormalised gates": lambda w: (w, 2, True),
    "the neighbour's weights": lambda w: (_neighbour(w, 0), 2, False),
    "fp8 weights": lambda w: (_fp8(w), 2, False),
}


@pytest.mark.parametrize("name", list(LAYER_MUTATIONS))
def test_the_layer_check_catches_each_mutation_and_passes_without(tiny, name):
    _, cfg, params, _, h = tiny
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    got = train_moe.layer_errors(h, LAYER_MUTATIONS[name](layer0), (layer0, 2, False))
    if name == "none":
        assert got["max"] <= train_moe.LAYER_RTOL / 2
        assert got["rows"] == 2 * h.shape[0] and got["dropped"] == 0
    else:
        assert got["mean"] > 2 * train_moe.LAYER_RTOL, got


def _whole_model_reading(cfg, params, program_cfg, program_params, rows, arch):
    """As the rehearsal computes: float32 arithmetic on the bf16-valued weights
    (rehearse-moe.json says why)."""
    from ray_tpu.models import forward

    program_cfg = dataclasses.replace(program_cfg, dtype=jnp.float32)
    program_params = jax.tree.map(lambda a: a.astype(jnp.float32), program_params)
    prog = forward(program_params, rows, program_cfg)
    ref, routing = moe_decoder.logits(params, rows[0], **arch)
    err = np.asarray(moe_decoder.position_errors(prog[0], ref))
    tie = np.asarray(train_moe.near_ties(routing["probs"], arch["top_k"],
                                         train_moe.MODEL_TIE_GAP).any(axis=0))
    return err, tie


@pytest.mark.parametrize("name", ["none", "QK-norm left out", "fp8 weights"])
def test_the_logits_check_catches_each_mutation_and_passes_without(tiny, name):
    doc, cfg, params, rows, _ = tiny
    arch = train_moe.reference_arch(doc["model"])
    program_cfg, program_params = cfg, params
    if name == "QK-norm left out":
        program_cfg = dataclasses.replace(cfg, qk_norm=False)
        program_params = {**params, "layers": {
            k: v for k, v in params["layers"].items() if k not in ("q_norm", "k_norm")}}
    elif name == "fp8 weights":
        program_params = _fp8(params)
    err, tie = _whole_model_reading(cfg, params, program_cfg, program_params, rows, arch)
    if name == "none":
        assert err.max() <= 1e-4 and tie.mean() < 0.5
    else:
        assert np.median(err) > 2 * train_moe.LOGIT_MEDIAN_RTOL, (name, np.median(err))


def test_the_grouped_matmul_reader_matches_the_events_a_v5e_printed(cell):
    """Its ``what`` quotes one event of each kernel off the real trace (PR
    26); an op that only MENTIONS a kernel (its operand) is not that kernel."""
    from benchmark import trace_reduce

    reader = cell.readers["kernel.moe_gmm_share.train"]
    gmm = reader["what"].split("(PR 26): ")[1].split("; the other kernel's")[0]
    tgmm = reader["what"].split("events start ")[1].split(". It is part")[0] \
        + '%p), custom_call_target="tpu_custom_call"'
    fusion = "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %moe_gmm.112), kind=kLoop"
    flash = '%flash_fwd.17 = bf16[4]{0} custom-call(%p), custom_call_target="tpu_custom_call"'
    assert gmm.startswith("%moe_gmm.112 = ") and tgmm.startswith("%moe_tgmm.33 = ")
    ops = {gmm: [1.0, 3], tgmm: [2.0, 1], fusion: [4.0, 1], flash: [8.0, 1]}
    assert trace_reduce.matching(ops, reader["params"]["pattern"]) == (3.0, 4)
    roofline = cell.readers["kernel.moe_gmm_roofline.train"]["params"]
    assert roofline["num"] == ["moe.gmm_flops_per_call", "moe.gmm_calls"]
    assert roofline["den"] == ["moe.gmm_seconds", "train.peak_flops_per_s"]
