"""The configuration of latent attention over every causal key
(Kimi-K2-Instruct: its first five layers, a chip's share of the experts and
of the vocabulary), its counts, and the runner's limits against the controls
they are meant to refuse, at the rehearsal size on the CPU."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_mla, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_mla

CELL = "kimi-k2-instruct.train-mla-full"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["scope.mla_full_share.train", "moe.held_share"]


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Kimi-K2-Instruct")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms and the
    biases moved as the runner moves them, and one layer-check input."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-mla.json")) as f:
        doc = json.load(f)
    cfg = train_mla.model_config(doc["model"], doc["train"], remat_policy="attn",
                                 dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: train_mla.seed_biases(
        train_mla.seed_norms(init_params(cfg, key), key), key))(key)
    h = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.hidden), cfg.dtype)
    return doc, cfg, params, h, jax.tree.map(lambda a: a[0], params["layers"])


def test_the_configuration_keeps_every_published_number_but_the_three_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == "kimi-k2-instruct")
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/kimi-k2-instruct.json"
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    assert (model["num_hidden_layers"], model["n_routed_experts"], model["vocab_size"]) \
        == (5, 8, 20480)
    # the leading dense layer and four layers after it, 8 experts, an eighth of
    # the vocabulary: the guide's floors
    assert model["first_k_dense_replace"] == 1 and model["moe_layer_freq"] == 1
    assert model["vocab_size"] * 8 == 163840 and model["n_routed_experts"] * 48 == 384
    assert set(model) - set(row["config"]) == {"n_routed_experts_published", "router_width",
                                               "experts_held"}
    assert (model["router_width"], model["experts_held"], model["n_routed_experts_published"]) \
        == (384, [0, 7], 384)
    assert model["rope_scaling"] == {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
                                     "mscale_all_dim": 1,
                                     "original_max_position_embeddings": 4096, "type": "yarn"}
    # what the file owes its reader: the deployment, the count, the assumed
    # points, the memory readings with the choice, the map of the files
    assert "48 chips share each layer" in config["deployment"]
    assert "171" in config["deployment"] and "1/48" in config["deployment"]
    assert config["parameters"] == 2_792_120_832 and len(config["assumed"]) >= 8
    assert {"yarn", "router_bias", "balance", "optimizer", "rope_pairs"} <= set(config["assumed"])
    assert "8,192" in config["memory"] and "4,096" in config["memory"]
    assert config["train"]["batch"] == 1 and "latent_decoder.py" in config["files"]


def test_the_program_is_told_the_published_widths_and_the_share(cell):
    cfg = train_mla.model_config(cell.config["model"], cell.config["train"])
    assert cfg.lead_pattern == ("mla_full",) and cfg.layer_pattern == ("mla_full",)
    assert cfg.n_periods == 4 and cfg.mla is None and cfg.mla_window is None
    a = cfg.mla_full
    assert dataclasses.astuple(a.yarn) == (32.0, 4096, 1.0, 1.0, 1.0)
    assert (a.heads, a.q_rank, a.kv_rank, a.nope_dim, a.rope_dim, a.v_dim, a.rope_theta) \
        == (64, 1536, 512, 128, 64, 128, 5e4)
    assert (a.window, a.index_heads, a.rescale, a.gate) == (0, 0, False, False)
    # computed where the configuration is built, from factor and mscale_all_dim
    assert a.softmax_factor == (0.1 * math.log(32.0) + 1.0) ** 2
    assert abs(a.softmax_factor - 1.81326) < 1e-5
    assert (cfg.hidden, cfg.lead_intermediate, cfg.intermediate, cfg.moe_shared) \
        == (7168, 18432, 2048, 2048)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_routed_scale,
            cfg.moe_bias_rate) == (384, 8, (0, 8), "sigmoid", 2.827, 0.001)
    assert cfg.moe_norm_topk and not cfg.moe_shared_gate and not cfg.norm_plus_one
    assert (cfg.vocab_size, cfg.moe_aux_weight, cfg.moe_z_weight, cfg.norm_eps) \
        == (20480, 0.0001, 0.0, 1e-6)
    arch = train_mla.reference_arch(cell.config["model"])
    assert arch["spec"] == dataclasses.asdict(a)
    assert (arch["top_k"], arch["held_first"], arch["routed_scale"], arch["lead_layers"]) \
        == (8, 0, 2.827, 1)
    # another published group: the two factors follow its keys
    other = {**cell.config["model"], "rope_scaling": {
        **cell.config["model"]["rope_scaling"], "factor": 40, "mscale": 1.0,
        "mscale_all_dim": 0.707}}
    fields, factor = flops_mla.yarn(other)
    m = lambda x: 0.1 * x * math.log(40) + 1  # noqa: E731
    assert fields["attention_factor"] == m(1.0) / m(0.707) and factor == m(0.707) ** 2


def test_parameter_counts_by_hand(cell):
    model = cell.config["model"]
    mixer = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
             + 1536 + 512)
    expert, dense = 3 * 7168 * 2048, 3 * 7168 * 18432
    assert (mixer, expert, dense) == (101_124_096, 44_040_192, 396_361_728)
    assert flops_mla.mixer_params(model) == mixer - 2048
    dense_layer = mixer + dense + 2 * 7168
    expert_layer = mixer + 2 * 7168 + expert + 7168 * 384 + 384 + 8 * expert
    assert (dense_layer, expert_layer) == (497_500_160, 500_253_056)
    total = dense_layer + 4 * expert_layer + 2 * 7168 * 20480 + 7168
    assert flops_mla.param_count(model) == total == cell.config["parameters"] == 2_792_120_832
    # every width as published: 61 layers, 384 experts, 163,840 words
    whole = (mixer + dense + 2 * 7168
             + 60 * (mixer + 2 * 7168 + 385 * expert + 7168 * 384 + 384)
             + 2 * 7168 * 163840 + 7168)
    assert whole == pytest.approx(1026.41e9, rel=1e-5)


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    assert seq in (8192, 4096)
    want = lambda seq: {  # noqa: E731
        "scores": 5 * 2 * 64 * (192 + 128) * (seq + 1) / 2,
        "projections": 5 * 2 * (101_124_096 - 2048),
        "dense_mlp": 2 * 396_361_728,
        "router": 4 * 2 * 7168 * 384,
        "shared_expert": 4 * 2 * 44_040_192,
        "routed_experts": 4 * 2 * 8 * (8 / 384) * 44_040_192,
        "head": 2 * 7168 * 20480}
    for length, total, scores in ((8192, 10.11e9, 25), (4096, 8.85e9, 14)):
        parts = flops_mla.forward_flops_by_part(model, length)
        assert parts == pytest.approx(want(length), rel=1e-12)
        forward = sum(parts.values())
        assert 3 * forward == pytest.approx(total, rel=1e-3)
        assert round(100 * parts["scores"] / forward) == scores
        assert flops_mla.train_flops_per_token(model, length) == pytest.approx(3 * forward)
    parts = flops_mla.forward_flops_by_part(model, 8192)
    share = {k: round(100 * v / sum(parts.values())) for k, v in parts.items()}
    assert share == {"scores": 25, "projections": 30, "dense_mlp": 24, "router": 1,
                     "shared_expert": 10, "routed_experts": 2, "head": 9}
    cfg = train_mla.model_config(model, cell.config["train"])
    for length in (4096, 8192):
        assert train_flops_per_token(cfg, length) == pytest.approx(
            flops_mla.train_flops_per_token(model, length), rel=1e-12)


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes, and every
    attention kernel's operations and bytes against what it records of itself."""
    from ray_tpu.models.mla import mla_mixer
    from ray_tpu.ops import trace_log

    doc, cfg, params, h, layer = tiny
    model = doc["model"]
    assert flops_mla.param_count(model) == sum(leaf.size for leaf in jax.tree.leaves(params))
    seq = h.shape[0]

    def loss(layer):
        return mla_mixer(h[None], layer, cfg.mla_full, config=cfg,
                         positions=jnp.arange(seq))[0].sum()

    jax.jit(jax.grad(loss))(layer)
    recorded = trace_log.kernel_costs()
    want = flops_mla.attention_kernel_costs(model, 1, seq)
    assert sorted(want) == ["flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]
    for kernel, (kernel_flops, kernel_bytes) in want.items():
        got = recorded[kernel]
        # the rehearsal computes in float32: 4-byte operands where the count has bf16's 2
        assert got["flops"] == pytest.approx(kernel_flops, rel=1e-12), kernel
        assert kernel_bytes <= got["bytes"] <= 2 * kernel_bytes, kernel
    # at the cell's size: the triangle's pairs at 64 heads, 192 beside 128
    big = flops_mla.attention_kernel_costs(cell.config["model"], 1, 8192)
    pairs = 8192 * 8193 / 2
    assert big["flash_fwd"][0] == 2 * 64 * pairs * (192 + 128)
    assert big["flash_bwd_dq"][0] == 2 * 64 * pairs * (192 + 128 + 192)
    assert big["flash_bwd_dkdv"][0] == 2 * 64 * pairs * (192 + 128 + 128 + 192)
    q_b, o_b, stats = 64 * 8192 * 192 * 2, 64 * 8192 * 128 * 2, 64 * 8192 * 4
    assert big["flash_fwd"][1] == q_b + o_b + (q_b + o_b) + 128 * stats
    assert big["flash_bwd_dkdv"][1] == q_b + o_b + (q_b + o_b) + 2 * stats + (q_b + o_b)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops_mla.roofline_seconds(*big["flash_fwd"], peaks) == big["flash_fwd"][0] / 197e12


def test_the_runner_refuses_a_model_type_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model of type"):
        train_mla.model_config({**cell.config["model"], "model_type": "laguna"},
                               cell.config["train"])


REFUSED_BY = {
    "fp8_weights": lambda e: min(e["mixer"]["max"], e["experts"]["max"]) > train_mla.MIXER_RTOL,
    "plain_rope": lambda e: e["mixer"]["max"] > train_mla.MIXER_RTOL,
    "no_mscale": lambda e: e["mixer"]["max"] > train_mla.MIXER_RTOL,
    "yarn_on_cos": lambda e: e["mixer"]["max"] > train_mla.MIXER_RTOL,
    "window_1024": lambda e: e["mixer"]["max"] > train_mla.MIXER_RTOL,
    "no_scale": lambda e: e["experts"]["max"] > train_mla.LAYER_RTOL,
    "sigmoid_held": lambda e: e["experts"]["max"] > train_mla.LAYER_RTOL
    and e["experts"]["held_share"] == 1.0
    and abs(e["experts"]["held_share"] - e["experts"]["ref_held_share"])
    > train_mla.HELD_SHARE_RTOL * e["experts"]["ref_held_share"],
}


def _layer_readings(tiny, control):
    doc, _, _, h, ref_layer = tiny
    cfg = train_mla.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                 dtype=jnp.float32)
    if control == "window_1024":  # at the rehearsal's 128 positions: a window that drops keys
        cfg = dataclasses.replace(cfg, mla_full=dataclasses.replace(cfg.mla_full, window=16))
    layer = ref_layer
    if control == "fp8_weights":
        layer = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), layer)
    return train_mla.layer_errors(cfg, train_mla.reference_arch(doc["model"]), layer,
                                  ref_layer, h, control)


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # the two others leave every layer as it is and change the compared step:
    # tests/test_latent_full_model.py puts them through ``step_errors``
    assert set(REFUSED_BY) | {"half_batch", "unchanged_state"} == set(train_mla.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert max(e["mixer"]["max"], e["experts"]["max"]) < 1e-4
    assert abs(e["experts"]["held_share"] - e["experts"]["ref_held_share"]) < 1e-6
    assert not any(refuses(e) for refuses in REFUSED_BY.values())
    assert e["experts"]["dropped"] == 0 and e["experts"]["rows"] == 128 * 3


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_scope():
    tail = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    scope = lambda path: f', frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}'  # noqa: E731
    flash = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"]
    ops = {f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
           + scope("stack/attn/mla_full"): [1.0, 2] for i, n in enumerate(flash)}
    ops["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/attn/mla_full")] = [2.0, 4]
    ops["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" + scope("stack/attn/mla_q")] = [
        1.0, 1]
    ops["%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/mlp/moe_experts")] = [1.0, 1]
    manifest = Manifest()
    readers = {m: json.load(open(manifest.reader_file(m))) for m in NEW_METRICS}
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, reader in readers.items():
        assert CELL in declared[name]["workloads"]
        for k in ("layer", "unit", "moves"):
            assert reader[k] == declared[name][k]
    assert {declared[m]["layer"] for m in NEW_METRICS} == {"models/mla", "models/moe routing"}

    def read(ops):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "moe": {"held_share": 0.0208}}
        return layer_metrics.read_all(readers, obs)

    assert read(ops) == {"scope.mla_full_share.train": 25.0, "moe.held_share": 0.0208}
    # no such scope (a CPU rehearsal; an older program): it reads 0
    bare = {"%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
            + scope("stack/attn"): [1.0, 1]}
    assert read(bare)["scope.mla_full_share.train"] == 0.0
    pattern = readers["scope.mla_full_share.train"]["params"]["pattern"]
    hits = lambda paths: trace_reduce.matching(  # noqa: E731
        {f"%fusion.{i} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p.{i}), kind=kLoop" + scope(p): [1.0, 1]
         for i, p in enumerate(paths)}, pattern)[1]
    assert hits(["mla_full", "stack/attn/mla_full", "stack/attn/mla_full/x"]) == 3
    assert hits(["stack/attn/mla_full_x", "stack/attn/xmla_full", "stack/attn/mla_q",
                 "stack/attn", ""]) == 0
    # an op that only MENTIONS the scope (an operand's name) is not in it
    assert trace_reduce.matching(
        {"%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %mla_full.1), kind=kLoop": [1.0, 1]},
        pattern)[1] == 0
    # the cell reports every metric that lists it, the two new ones among them
    joined = {m["name"] for m in manifest.doc["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= joined == set(manifest.cell(CELL).per_layer)
    assert {"kernel.flash_roofline.train", "kernel.moe_gmm_roofline.train", "train.mfu",
            "scope.mla_q_share.train", "scope.mla_kv_share.train"} <= joined


def test_the_cell_declares_its_scope_metrics_in_the_traced_run_only(cell):
    want = {f"scope.{s}_share.train" for s in (
        "attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped", "moe_route",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "mla_q", "mla_kv",
        "mla_full")}
    assert want == {m for m in cell.declared(True) if m.startswith("scope.")}
    assert not [m for m in cell.declared(False) if m.startswith("scope.")]
    assert set(cell.declared(False)) == {"train_tok_s_chip", "setup_s"}


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)``:
    the six controls that keep the leaves change that config (the one that
    reshapes leaves stands in ``layer_errors`` alone, above), and nothing else
    of it."""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_mla.model_config(model, sizes)
    changed = {}
    for control in train_mla.CONTROLS:
        cfg = train_mla.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {"fp8_weights": set(), "plain_rope": {"mla_full"},
                       "no_mscale": {"mla_full"}, "yarn_on_cos": {"mla_full"},
                       "window_1024": {"mla_full"}, "no_scale": {"moe_routed_scale"},
                       "sigmoid_held": set(), "half_batch": set(), "unchanged_state": set()}
    spec = lambda control: train_mla.model_config(model, sizes, control).mla_full  # noqa: E731
    assert spec("plain_rope") == dataclasses.replace(true.mla_full, yarn=None)
    assert spec("no_mscale") == dataclasses.replace(true.mla_full, softmax_factor=1.0)
    on_cos = spec("yarn_on_cos")
    assert on_cos.softmax_factor == 1.0
    assert abs(on_cos.yarn.attention_factor - 1.34657) < 1e-5
    assert spec("window_1024").window == 1024
    assert train_mla.model_config(model, sizes, "no_scale").moe_routed_scale == 1.0
    assert set(train_mla.LAYER_CONTROLS) == {"sigmoid_held"}


def test_an_unknown_control_is_refused_before_a_cluster_starts(monkeypatch, cell):
    from benchmark.runners import Context

    monkeypatch.setenv("BENCH_MLA_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_MLA_CONTROL"):
        train_mla.run(ctx)
