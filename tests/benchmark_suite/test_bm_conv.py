"""The configuration of double-gated short convolutions beside roped, per-head-
normed attention (lfm2-8b-a1b: its first 18 layers, a quarter of its experts and
of its tied vocabulary), its counts, its readers, and the runner's limits
against the controls they are meant to refuse, at the rehearsal size on the
CPU. The cell's rehearsal in both trace modes is
``test_bm_rehearsal.py``'s, which runs every cell of the manifest."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_conv, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_conv

CELL = "lfm2-8b-a1b.train-8k-conv"
CONFIG = "lfm2-8b-a1b"
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
LEAD, PERIOD = ["conv", "conv"], ["full_attention", "conv", "conv", "conv"]
NEW_METRICS = ["scope.sconv_proj_share.train", "scope.sconv_mix_share.train",
               "scope.sconv_out_share.train", "sconv.past_share"]


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "LFM2-8B-A1B")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms and the
    biases moved as the runner moves them, and the inputs of a layer check."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-conv.json")) as f:
        doc = json.load(f)
    cfg = train_conv.model_config(doc["model"], doc["train"], remat_policy="attn",
                                  dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: train_conv.seed_leaves(init_params(cfg, key), key))(key)
    h, g = (jax.random.normal(jax.random.PRNGKey(k), (64, cfg.hidden), cfg.dtype) for k in (5, 6))
    return doc, cfg, params, (h, g)


def test_the_configuration_keeps_every_published_number_but_the_four_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/lfm2-8b-a1b.json"
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key == "layer_types":
                assert where[key] == value[:18] == LEAD + PERIOD * 4, key
            elif key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    assert set(model) == set(row["config"]) | {"num_experts_published", "experts_held",
                                               "tie_word_embeddings"}
    assert (model["num_hidden_layers"], model["num_experts"], model["vocab_size"] * 4) == (
        18, 8, 65536)
    assert (model["num_experts_published"], model["experts_held"]) == (32, [0, 7])
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (24, 32, 65536)
    assert config["layer_types_published"] == row["config"]["layer_types"]
    assert {k: config[k] for k in row["config"]} == {k: model[k] for k in row["config"]}
    # every cut holds the guide's floors: four layers and more after the
    # leading ones in whole periods, 8 experts, an eighth of the vocabulary
    assert set(config["reduced_why"]) == set(REDUCED)
    # what the file owes its reader: the deployment, the assumed points, the
    # memory readings of every choice of the rule with the one taken, the map
    assert "expert-parallel" in config["deployment"] and "18-23" in config["deployment"]
    assert {"tie_word_embeddings", "head_dim", "qk_norm", "rope", "in_proj_order", "conv",
            "final_norm", "router", "expert_bias", "balance", "weights", "optimizer",
            "rows"} <= set(config["assumed"])
    for choice in ("(a)", "(b)", "(c)", "(d)", "TAKEN"):
        assert choice in config["memory"], choice
    for name in ("conv_moe_decoder.py", "train_conv.py", "flops_conv.py", "rehearse-conv.json",
                 "train-8k-conv.json", "short_conv.py", "test_bm_conv.py"):
        assert name in config["files"], name
    assert config["chips"] == 1 and config["train"]["batch"] in (2, 4)


def test_the_program_is_told_the_published_widths(cell):
    model = cell.config["model"]
    cfg = train_conv.model_config(model, cell.config["train"])
    assert cfg.lead_pattern == ("sconv", "sconv") and cfg.n_periods == 4
    assert cfg.layer_pattern == ("attn", "sconv", "sconv", "sconv")
    assert (cfg.hidden, cfg.sconv_taps, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 3, 32, 8, 64)
    assert (cfg.rope_theta, cfg.rotary_dim, cfg.head_qk_norm, cfg.qk_norm, cfg.norm_plus_one,
            cfg.attn_out_gate, cfg.norm_eps) == (1e6, 0, True, False, False, False, 1e-5)
    assert (cfg.lead_intermediate, cfg.intermediate, cfg.vocab_size) == (7168, 1792, 16384)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_norm_topk,
            cfg.moe_routed_scale, cfg.moe_shared) == (32, 4, (0, 8), "sigmoid", True, 1.0, 0)
    assert (cfg.moe_bias_rate, cfg.moe_aux_weight, cfg.moe_z_weight) == (0.001, 0.0001, 0.0)
    assert cfg.tie_embeddings is True and cfg.remat_policy == "full"
    arch = train_conv.reference_arch(model)
    assert arch["attn"] == {"heads": 32, "kv_heads": 8, "head_dim": 64, "rope_theta": 1e6}
    assert (arch["pattern"], arch["lead_pattern"]) == (cfg.layer_pattern, cfg.lead_pattern)
    assert (arch["top_k"], arch["norm_topk"], arch["held_first"], arch["routed_scale"],
            arch["faults"]) == (4, True, 0, 1.0, frozenset())
    # the lead and the period are read off ``layer_types``; the published order's
    # tail breaks the period, and a layer type the row does not have is no kind
    published = flops_conv.published(
        model, num_hidden_layers=24, layer_types=cell.config["layer_types_published"])
    assert len(flops_conv.lead_and_period(published)[1]) == 22
    with pytest.raises(KeyError):
        flops_conv.layer_kinds({**model, "layer_types": ["mamba"] * 18})


def test_parameter_counts_and_flops_by_hand_and_equal_to_the_programs(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    assert (conv, attention, dense, expert) == (16_783_360, 10_485_888, 44_040_192, 11_010_048)
    held = (14 * conv + 4 * attention + 18 * 4096 + 2 * dense + 16 * (8 * expert + 65_536)
            + 16384 * 2048 + 2048)
    assert held == 1_808_955_904 == flops_conv.param_count(model) == cell.config["parameters"]
    assert flops_conv.bias_count(model) == 16 * 32
    whole = (18 * conv + 6 * attention + 24 * 4096 + 2 * dense + 22 * (32 * expert + 65_536)
             + 65536 * 2048 + 2048)
    published = flops_conv.published(
        model, num_hidden_layers=24, layer_types=cell.config["layer_types_published"],
        vocab_size=cell.config["vocab_size_published"])
    assert whole == 8_339_929_856 == flops_conv.param_count(published)
    assert flops_conv.bias_count(published) == 22 * 32
    assert "8,339,929,856" in cell.config["parameters_note"]
    assert seq == 8192
    want = {"conv_products": 14 * 2 * 4 * 2048 * 2048,
            "attention_products": 4 * 2 * (2 * 2048 * 2048 + 2 * 2048 * 512),
            "attention_scores": 4 * 2 * 32 * 64 * seq,
            "dense_mlp": 2 * 2 * dense, "router": 16 * 2 * 2048 * 32,
            "routed_experts": 16 * 2 * 4 * (8 / 32) * expert, "head": 2 * 2048 * 16384}
    assert flops_conv.forward_flops_by_part(model, seq) == pytest.approx(want, rel=1e-12)
    forward = sum(want.values())
    assert flops_conv.train_flops_per_token(model, seq) == pytest.approx(3 * forward, rel=1e-12)
    cfg = train_conv.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    share = {k: 100 * v / forward for k, v in want.items()}
    # ISSUE 56's arithmetic: the new mixer's products and the held experts are
    # 64% of the FLOPs at 8k, attention's scores a tenth
    assert 36 < share["conv_products"] < 37 and 27 < share["routed_experts"] < 28
    assert 10 < share["attention_scores"] < 11 and 13 < share["dense_mlp"] < 14


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes, and the flash
    kernels' operations against what they record of themselves."""
    doc, cfg, params, _ = tiny
    model = doc["model"]
    assert flops_conv.param_count(model) + flops_conv.bias_count(model) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    big = cell.config["model"]
    flash = flops_conv.flash_kernel_costs(big, 4, 8192)
    assert flash["flash_fwd"][0] == 2 * 4 * 32 * 8192 * 8192 / 2 * 2 * 64
    assert flash["flash_bwd_dkdv"][0] == 2 * flash["flash_fwd"][0]
    gmm = flops_conv.grouped_matmul_costs(big, 32768.0)
    assert gmm == (2.0 * 32768 * 2048 * 1792, 2.0 * (32768 * 3840 + 8 * 2048 * 1792))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 4,096 rows an expert: ~770 FLOP a byte, the operations bind on a chip of 240
    assert flops_conv.roofline_seconds(*gmm, peaks) == gmm[0] / 197e12


def test_the_runner_refuses_a_model_or_a_control_it_does_not_know(monkeypatch, cell):
    from benchmark.runners import Context

    with pytest.raises(RunFailure, match="builds no model of type"):
        train_conv.model_config({**cell.config["model"], "model_type": "lfm2"},
                                cell.config["train"])
    monkeypatch.setenv("BENCH_CONV_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_CONV_CONTROL"):
        train_conv.run(ctx)


LIMITS = train_conv
REFUSED_BY = {
    "conv_left_out": lambda e: e["conv"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "taps_reversed": lambda e: e["conv"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "thirds_x_b_c": lambda e: e["conv"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "fp8_weights": lambda e: min(e["conv"]["out"]["max"], e["attention"]["out"]["max"])
    > LIMITS.MIXER_RTOL,
    "no_qk_norm": lambda e: e["attention"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "softmax_router": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL,
    "no_c_gate": lambda e: e["conv"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "silu_after_conv": lambda e: e["conv"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "no_rope": lambda e: e["attention"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "bias_on_gates": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL,
}


def _layer_readings(tiny, control):
    doc, _, params, (h, g) = tiny
    cfg = train_conv.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                  dtype=jnp.float32)
    program = params
    if control == "fp8_weights":
        program = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                               if a.ndim > 1 else a, params)
    program = train_conv.planted(program, control)
    pick = lambda tree: (tree["lead_layers"]["layer1"],  # noqa: E731
                         jax.tree.map(lambda a: a[0], tree["layers"]["slot0"]))
    return train_conv.layer_errors(cfg, train_conv.reference_arch(doc["model"], control),
                                   pick(program), pick(params), h, g)


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # ``reference_default_precision`` is the backend's own float32 on a CPU,
    # and the last two change the compared step: tests/test_gqa_window_model.py
    # puts them through the ``step_errors`` this runner imports
    assert set(REFUSED_BY) | {"reference_default_precision", "half_batch",
                              "unchanged_state"} == set(train_conv.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert max(e[kind][what]["max"] for kind in ("conv", "attention")
               for what in ("out", "grad")) < 1e-4
    assert e["experts"]["max"] < 1e-4 and e["experts"]["dropped"] == 0
    assert e["experts"]["held_share"] == pytest.approx(e["experts"]["ref_held_share"])
    assert not any(refuses(e) for refuses in REFUSED_BY.values())


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)`` on
    ``planted`` leaves: the controls of the program change those, the
    reference's change ``reference_arch``, and nothing else moves."""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_conv.model_config(model, sizes)
    changed = {}
    for control in train_conv.CONTROLS:
        cfg = train_conv.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {**{c: set() for c in train_conv.CONTROLS},
                       "no_qk_norm": {"head_qk_norm"}, "softmax_router": {"moe_score"}}
    taps = jnp.arange(6.0).reshape(3, 2) + 1
    leaves = {"lead_layers": {"layer0": {"conv": taps, "w_in": jnp.arange(12.0).reshape(2, 3, 2)}},
              "layers": {"slot1": {"conv": taps[None], "wq": jnp.ones(4)}}}
    out = train_conv.planted(leaves, "conv_left_out")
    assert out["lead_layers"]["layer0"]["conv"].tolist() == [[0, 0], [0, 0], [1, 1]]
    assert out["layers"]["slot1"]["conv"].tolist() == [[[0, 0], [0, 0], [1, 1]]]
    out = train_conv.planted(leaves, "taps_reversed")
    assert out["layers"]["slot1"]["conv"][0].tolist() == taps[::-1].tolist()
    out = train_conv.planted(leaves, "thirds_x_b_c")
    w_in = leaves["lead_layers"]["layer0"]["w_in"]
    assert out["lead_layers"]["layer0"]["w_in"][:, 0].tolist() == w_in[:, 2].tolist()
    assert out["lead_layers"]["layer0"]["w_in"][:, 1].tolist() == w_in[:, 0].tolist()
    assert train_conv.planted(leaves, "no_rope") is leaves
    for control in train_conv.CONTROLS:
        faults = train_conv.reference_arch(model, control)["faults"]
        assert faults == (frozenset({control}) if control in train_conv.FAULTS else frozenset())


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_scopes(cell):
    scope = lambda path: (  # noqa: E731
        f', frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}')
    ops = {}
    for i, (name, seconds) in enumerate((("sconv_proj", 4.0), ("sconv_mix", 3.0),
                                         ("sconv_out", 2.0))):
        ops[f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"
            + scope(f"stack/attn/{name}")] = [seconds, 1]
    manifest = Manifest()
    readers = {m: json.load(open(manifest.reader_file(m))) for m in NEW_METRICS}
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, reader in readers.items():
        assert declared[name]["workloads"] == [CELL]
        for k in ("layer", "unit", "moves"):
            assert reader[k] == declared[name][k]
        assert name in cell.declared(True) and name not in cell.declared(False)
    assert {declared[m]["layer"] for m in NEW_METRICS} == {"models/short_conv"}
    assert declared["sconv.past_share"]["source"] == "program_counter"

    def read(ops):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "sconv": {"past_share": 0.66}}
        return layer_metrics.read_all(readers, obs)

    assert read(ops) == {
        "scope.sconv_proj_share.train": 20.0, "scope.sconv_mix_share.train": 15.0,
        "scope.sconv_out_share.train": 10.0, "sconv.past_share": 0.66}
    # no such scope (an older program): each reads 0
    bare = {"%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
            + scope("stack/attn"): [1.0, 1]}
    none = read(bare)
    assert [none[m] for m in NEW_METRICS[:3]] == [0.0] * 3
    # an op that only MENTIONS a scope (an operand's name) is not in it
    mention = {"%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %sconv_mix.1), kind=kLoop": [1.0, 1]}
    assert trace_reduce.matching(
        mention, readers["scope.sconv_mix_share.train"]["params"]["pattern"]) == (0.0, 0)


def test_the_cell_joins_the_accepted_metrics_that_are_true_of_it(cell):
    """The step's own metrics, the top-level scopes, the passes, the three plain
    flash kernels' shares (its attention layers call them), the grouped matmul's
    and the routing's; no other configuration's kernels or scopes, and no shared
    expert's."""
    manifest = Manifest()
    joined = {m["name"] for m in manifest.doc["per_layer"] if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "train.mfu", "train.step_ms", "train.data_wait_ms", "train.report_ms",
        "device.idle_share.train", "kernel.custom_call_share.train",
        *(f"kernel.flash_{part}_share.train" for part in ("fwd", "bwd_dq", "bwd_dkdv")),
        "kernel.moe_gmm_share.train", "kernel.moe_gmm_roofline.train",
        "moe.load_max_over_mean", "moe.held_share",
        *(f"scope.{s}_share.train" for s in (
            "attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped")),
        *(f"scope.moe_{s}_share.train" for s in ("route", "dispatch", "experts", "combine")),
        *(f"pass.{s}_share.train" for s in (
            "fwd", "remat", "bwd", "none", "remat_attn", "remat_mlp"))}
    assert set(cell.declared(False)) == {"train_tok_s_chip", "setup_s"}
    assert cell.chips == 1 and cell.config_name == CONFIG and cell.traffic_name == "train-8k-conv"
    assert any(c["name"] == CONFIG for c in manifest.doc["configs"])
    assert manifest.problems() == []
