"""The configuration whose router reads the block's input ahead of attention
(SmallThinker-21BA3B-Instruct: its first periods of one un-roped full layer
and three 4,096-key window layers, a chip's share of the ReGLU experts and of
the vocabulary), its counts, and the runner's limits against the controls they
are meant to refuse, at the rehearsal size on the CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_prerouted, layer_metrics
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_prerouted

CELL = "smallthinker-21ba3b-instruct.train-16k-win4k"
CONFIG = "smallthinker-21ba3b-instruct"
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts", "vocab_size"]
LAYOUTS = ("rope_layout", "sliding_window_layout")
ADDED = {"router_width", "experts_held", "moe_num_primary_experts_published"}


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms moved
    as the runner moves them, and the two inputs of a layer check."""
    with open(os.path.join(HERE, "rehearse-prerouted.json")) as f:
        doc = json.load(f)
    cfg = train_prerouted.model_config(doc["model"], doc["train"], remat_policy="attn",
                                       dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: train_prerouted.seeded_weights(cfg, key, 0.5))(key)
    h = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.hidden), cfg.dtype)
    x_in = jax.random.normal(jax.random.PRNGKey(6), (128, cfg.hidden), cfg.dtype)
    pick = lambda slot: jax.tree.map(lambda a: a[0], params["layers"][slot])  # noqa: E731
    return doc, cfg, params, (h, x_in), (pick(f"slot{cfg.layer_pattern.index('gqa')}"),
                                         pick(f"slot{cfg.layer_pattern.index('gqa_win')}"))


def test_the_configuration_keeps_every_published_number_but_the_five_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    depth = model["num_hidden_layers"]
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key in LAYOUTS:
                assert where[key] == value[:depth], key
            elif key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    # whole periods of (full, window, window, window), the guide's floors: at
    # least a period and four layers, 8 experts, an eighth of the vocabulary
    assert depth in (8, 12) and model["rope_layout"] == [0, 1, 1, 1] * (depth // 4)
    assert model["sliding_window_layout"] == model["rope_layout"]
    assert (model["moe_num_primary_experts"], model["vocab_size"]) == (16, 37984)
    assert model["vocab_size"] * 4 == 151936 and model["moe_num_primary_experts"] * 4 == 64
    assert set(model) - set(row["config"]) == ADDED
    assert (model["router_width"], model["experts_held"],
            model["moe_num_primary_experts_published"]) == (64, [0, 15], 64)
    assert {k: config[k] for k in model} == model
    # what the file owes its reader: the deployment, the 1/4 of rows, the
    # count, the assumed points, the memory readings with the choice, the map
    assert "4 chips share each layer" in config["deployment"]
    assert "1,536" in config["deployment"] and "6,144 (1/4)" in config["deployment"]
    assert {"router_input", "experts_gate", "gates", "not_there", "balance", "rope_pairs",
            "weights", "optimizer"} <= set(config["assumed"])
    assert "READING" in config["assumed"]["router_input"]
    assert "12 layers" in config["memory"] and "8 layers" in config["memory"]
    assert config["train"]["batch"] == 1 and "prerouted_moe_decoder.py" in config["files"]
    assert config["chips"] == 1


def test_the_program_is_told_the_published_widths_and_the_share(cell):
    model = cell.config["model"]
    cfg = train_prerouted.model_config(model, cell.config["train"])
    assert cfg.lead_pattern == () and cfg.n_periods == model["num_hidden_layers"] // 4
    assert cfg.layer_pattern == ("gqa", "gqa_win", "gqa_win", "gqa_win")
    assert (cfg.gqa.heads, cfg.gqa.kv_heads, cfg.gqa.head_dim, cfg.gqa.rope_theta,
            cfg.gqa.window, cfg.gqa.gate, cfg.gqa.yarn) == (28, 4, 128, 0.0, 0, "none", None)
    assert cfg.gqa_window == dataclasses.replace(cfg.gqa, rope_theta=1.5e6, window=4096)
    assert (cfg.hidden, cfg.intermediate, cfg.moe_shared, cfg.lead_intermediate) \
        == (2560, 768, 0, 0)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_routed_scale) \
        == (64, 6, (0, 16), "softmax", 1.0)
    assert (cfg.moe_router_input, cfg.moe_activation, cfg.moe_norm_topk) \
        == ("block", "relu", True)
    assert (cfg.vocab_size, cfg.moe_aux_weight, cfg.moe_z_weight, cfg.norm_eps) \
        == (37984, 0.001, 0.0, 1e-6)
    arch = train_prerouted.reference_arch(model)
    assert arch["kinds"]["gqa_win"] == {"heads": 28, "kv_heads": 4, "head_dim": 128,
                                        "rope_theta": 1.5e6, "window": 4096}
    assert arch["kinds"]["gqa"] == {**arch["kinds"]["gqa_win"], "rope_theta": 0.0, "window": 0}
    assert (arch["top_k"], arch["held_first"], arch["pattern"]) == (6, 0, cfg.layer_pattern)
    # the period is read off the two layouts: the shortest unit that repeats;
    # a layer that is roped and full, or un-roped under a window, is no kind here
    assert flops_prerouted.period({**model, "num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
                                   "sliding_window_layout": [0, 1, 1, 1]}) == list(
        cfg.layer_pattern)
    with pytest.raises(KeyError):
        flops_prerouted.layer_kinds({**model, "rope_layout": [1] * len(model["rope_layout"])})


def test_parameter_counts_by_hand(cell):
    model = cell.config["model"]
    mixer = 2560 * 28 * 128 + 2 * 2560 * 4 * 128 + 3584 * 2560
    expert, router, norms = 3 * 2560 * 768, 2560 * 64, 2 * 2560
    assert (mixer, expert, router) == (20_971_520, 5_898_240, 163_840)
    layer = mixer + 16 * expert + router + norms
    assert layer == 115_512_320
    depth = model["num_hidden_layers"]
    total = depth * layer + 2 * 37984 * 2560 + 2560
    assert flops_prerouted.param_count(model) == total == cell.config["parameters"]
    assert total == {12: 1_580_628_480, 8: 1_118_579_200}[depth]
    # every width as published: 52 layers, 64 experts, 151,936 words
    whole_layer = mixer + 64 * expert + router + norms
    assert whole_layer == 398_627_840
    assert 52 * whole_layer + 2 * 151936 * 2560 + 2560 == 21_506_562_560
    assert "21,506,562,560" in cell.config["parameters_published_note"]


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    assert seq == 16384 == model["max_position_embeddings"]
    depth = model["num_hidden_layers"]
    band = (4096 * 4097 / 2 + (seq - 4096) * 4096) / seq          # keys a query keeps
    assert band * seq == 58_722_304
    assert flops_prerouted.window_share(seq, 4096) == pytest.approx(
        58_722_304 / (seq * (seq + 1) / 2), rel=1e-12)
    assert flops_prerouted.window_share(seq, 4096) == pytest.approx(0.4375, abs=5e-5)
    want = {"full_scores": depth // 4 * 2 * 28 * 256 * (seq + 1) / 2,
            "window_scores": 3 * depth // 4 * 2 * 28 * 256 * band,
            "projections": depth * 2 * 20_971_520,
            "router": depth * 2 * 2560 * 64,
            "experts": depth * 2 * 6 * (16 / 64) * 3 * 2560 * 768,
            "head": 2 * 2560 * 37984}
    parts = flops_prerouted.forward_flops_by_part(model, seq)
    assert parts == pytest.approx(want, rel=1e-12)
    forward = sum(want.values())
    assert flops_prerouted.train_flops_per_token(model, seq) == pytest.approx(
        3 * forward, rel=1e-12)
    cfg = train_prerouted.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    if depth == 12:
        assert forward == pytest.approx(1.729e9, rel=1e-3)
        share = {k: round(100 * v / forward, 1) for k, v in want.items()}
        assert share == {"full_scores": 20.4, "window_scores": 26.7, "projections": 29.1,
                         "router": 0.2, "experts": 12.3, "head": 11.2}
        assert 3 * forward * seq == pytest.approx(85.0e12, rel=1e-3)
    # at 8k the scores are a smaller share, and half of a window layer's
    # queries keep every causal key
    at_8k = flops_prerouted.forward_flops_by_part(model, 8192)
    scores = lambda p: (p["full_scores"] + p["window_scores"]) / sum(p.values())  # noqa: E731
    assert scores(at_8k) < scores(parts) - 0.05


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes, and every
    attention kernel's operations and bytes against what it records of itself."""
    from ray_tpu.models.gqa import gqa_mixer
    from ray_tpu.ops import trace_log

    doc, cfg, params, (h, _), layers = tiny
    model = doc["model"]
    assert flops_prerouted.param_count(model) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    seq = h.shape[0]
    positions = jnp.arange(seq, dtype=jnp.int32)

    def loss(w, spec):
        return gqa_mixer(h[None], w, spec, config=cfg, positions=positions)[0].sum()

    jax.jit(jax.grad(loss), static_argnums=1)(layers[0], cfg.gqa)
    jax.jit(jax.grad(loss), static_argnums=1)(layers[1], cfg.gqa_window)
    recorded = trace_log.kernel_costs()
    want = {**flops_prerouted.attention_kernel_costs(model, "gqa", 1, seq),
            **flops_prerouted.attention_kernel_costs(model, "gqa_win", 1, seq)}
    assert sorted(want) == ["attn_win_bwd_dkdv", "attn_win_bwd_dq", "attn_win_fwd",
                            "flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]
    for kernel, (kernel_flops, kernel_bytes) in want.items():
        got = recorded[kernel]
        # the rehearsal computes in float32: 4-byte operands where the count has bf16's 2
        assert got["flops"] == pytest.approx(kernel_flops, rel=1e-12), kernel
        assert kernel_bytes <= got["bytes"] <= 2 * kernel_bytes, kernel
    # at the cell's size: the band's pairs and half the square, at 28 heads
    big = cell.config["model"]
    win = flops_prerouted.attention_kernel_costs(big, "gqa_win", 1, 16384)
    full = flops_prerouted.attention_kernel_costs(big, "gqa", 1, 16384)
    assert win["attn_win_fwd"][0] == 2 * 2 * 28 * 58_722_304 * 128
    assert full["flash_fwd"][0] == 2 * 2 * 28 * 16384 * 16384 / 2 * 128
    assert full["flash_bwd_dkdv"][0] == 2 * full["flash_fwd"][0]
    # dK and dV leave the backward kernel at the QUERY heads' count, seven
    # times the keys' own
    q_b, kv_b, stats = 28 * 16384 * 128 * 2, 2 * 4 * 16384 * 128 * 2, 28 * 16384 * 4
    assert win["attn_win_bwd_dkdv"][1] == 2 * q_b + kv_b + 2 * stats + 2 * q_b
    assert q_b == 117_440_512 == 7 * kv_b // 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops_prerouted.roofline_seconds(*full["flash_fwd"], peaks) \
        == full["flash_fwd"][0] / 197e12


def test_the_runner_refuses_a_model_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model named"):
        train_prerouted.model_config({**cell.config["model"], "model_name": "laguna"},
                                     cell.config["train"])


LIMITS = train_prerouted
REFUSED_BY = {
    "fp8_weights": lambda e: min(e["full"]["max"], e["window"]["max"], e["experts"]["max"])
    > LIMITS.MIXER_RTOL,
    "silu_experts": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL
    and e["experts"]["act_zero"] == -1.0,
    "router_after_attention": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL,
    "rope_on_full": lambda e: e["full"]["max"] > LIMITS.MIXER_RTOL,
    "no_rope_window": lambda e: e["window"]["max"] > LIMITS.MIXER_RTOL,
    "window_512": lambda e: e["window"]["max"] > LIMITS.MIXER_RTOL
    and abs(e["window"]["window_share"] - e["share"]) > LIMITS.WINDOW_SHARE_ATOL,
    "no_window": lambda e: e["window"]["max"] > LIMITS.MIXER_RTOL
    and abs(e["window"]["window_share"] - e["share"]) > LIMITS.WINDOW_SHARE_ATOL,
    "gates_not_renormalised": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL,
    "router_normed": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL,
    "router_over_held": lambda e: e["experts"]["max"] > LIMITS.LAYER_RTOL
    and e["experts"]["held_share"] == 1.0,
}


def _layer_readings(tiny, control):
    doc, _, _, (h, x_in), ref_layers = tiny
    cfg = train_prerouted.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                       dtype=jnp.float32)
    if control == "window_512":  # the rehearsal's window is 9: a narrower one there is 4
        cfg = dataclasses.replace(cfg, gqa_window=dataclasses.replace(cfg.gqa_window, window=4))
    layers = ref_layers
    if control == "fp8_weights":
        layers = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), layers)
    out = train_prerouted.layer_errors(cfg, train_prerouted.reference_arch(doc["model"]), layers,
                                       ref_layers, h, x_in, control)
    out["share"] = flops_prerouted.window_share(h.shape[0], doc["model"]["sliding_window_size"])
    return out


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # the two others leave every layer as it is and change the compared step:
    # tests/test_gqa_window_model.py puts them through the ``step_errors`` this
    # runner imports
    assert set(REFUSED_BY) | {"half_batch", "unchanged_state"} == set(train_prerouted.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert max(e["full"]["max"], e["window"]["max"], e["experts"]["max"]) < 1e-4
    assert abs(e["window"]["window_share"] - e["share"]) < 1e-6
    assert not any(refuses(e) for refuses in REFUSED_BY.values())
    assert e["experts"]["dropped"] == 0 and e["experts"]["rows"] == 128 * 3
    assert 0 < e["experts"]["tokens_with_no_held_expert"] < 128
    assert 0.3 < e["experts"]["act_zero"] < 0.7


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)``:
    the controls that keep the leaves change that config (the two of
    LAYER_CONTROLS stand in ``layer_errors`` alone, above), and nothing else
    of it."""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_prerouted.model_config(model, sizes)
    changed = {}
    for control in train_prerouted.CONTROLS:
        cfg = train_prerouted.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {
        "fp8_weights": set(), "silu_experts": {"moe_activation"},
        "router_after_attention": {"moe_router_input"}, "rope_on_full": {"gqa"},
        "no_rope_window": {"gqa_window"}, "window_512": {"gqa_window"},
        "no_window": {"gqa_window"}, "gates_not_renormalised": {"moe_norm_topk"},
        "router_normed": set(), "router_over_held": set(), "half_batch": set(),
        "unchanged_state": set()}
    build = lambda control: train_prerouted.model_config(model, sizes, control)  # noqa: E731
    assert build("no_window").gqa_window.window >= 16384
    assert build("window_512").gqa_window.window == 512
    assert build("rope_on_full").gqa.rope_theta == true.gqa_window.rope_theta
    assert build("no_rope_window").gqa_window.rope_theta == 0.0
    assert set(train_prerouted.LAYER_CONTROLS) == {"router_normed", "router_over_held"}


@pytest.mark.parametrize("group,grad,update", [
    ("factored", (0.03, "wq"), (0.02, "wq")),
    # the norm is worse but too small a leaf for ``update``; ``few`` moves too little
    ("elementwise", (0.30, "norm"), (0.09, "router"))])
def test_the_steps_readings_are_judged_apart_by_how_adafactor_keeps_a_leaf(group, grad, update):
    """A leaf whose state holds a row of means is ``factored``; one kept
    element by element reads the gradient's element-wise gap (PERF.md section
    6), so each group has limits of its own, the factored the tighter."""
    import numpy as np

    step = {"by_leaf": {
        "grad_stats": {"wq": 0.03, "embed": 0.01, "router": 0.14, "norm": 0.30, "few": 0.2},
        "update": {"wq": 0.02, "embed": 0.01, "router": 0.09, "norm": 0.5, "few": 0.6},
        "ref_moved_share": {"wq": 0.2, "embed": 0.07, "router": 1.0, "norm": 0.5, "few": 0.001}}}
    v_row = {"wq": np.zeros(8), "embed": np.zeros(4), "router": np.zeros(1),
             "norm": np.zeros(1), "few": np.zeros(1)}
    sizes = {"wq": 4096, "embed": 2048, "router": 2048, "norm": 64, "few": 4096}
    got = train_prerouted.by_factoring(step, v_row, sizes)[group]
    assert (got["grad_stats"]["worst"], got["grad_stats"]["leaf"]) == grad
    assert (got["update"]["worst"], got["update"]["leaf"]) == update
    assert train_prerouted.GRAD_STATS_FACTORED_RTOL < train_prerouted.GRAD_STATS_RTOL
    assert train_prerouted.UPDATE_ALONG_FACTORED_ATOL < train_prerouted.UPDATE_ALONG_ATOL


def test_a_group_with_no_leaf_to_judge_reads_nothing():
    import numpy as np

    step = {"by_leaf": {"grad_stats": {"a": 0.5}, "update": {"a": 0.5},
                        "ref_moved_share": {"a": 0.5}}}
    got = train_prerouted.by_factoring(step, {"a": np.zeros(8)}, {"a": 4096})
    assert got["elementwise"] == {"grad_stats": {"worst": 0.0, "leaf": None},
                                  "update": {"worst": 0.0, "leaf": None}}
    assert got["factored"]["update"] == {"worst": 0.5, "leaf": "a"}


def test_an_unknown_control_is_refused_before_a_cluster_starts(monkeypatch, cell):
    from benchmark.runners import Context

    monkeypatch.setenv("BENCH_PREROUTED_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_PREROUTED_CONTROL"):
        train_prerouted.run(ctx)


def test_the_new_reader_parses_and_reads_nothing_where_the_counter_is_absent(cell):
    manifest = Manifest()
    with open(manifest.reader_file("moe.act_zero_share")) as f:
        reader = json.load(f)
    declared = next(m for m in manifest.doc["per_layer"] if m["name"] == "moe.act_zero_share")
    for k in ("layer", "unit", "moves"):
        assert reader[k] == declared[k]
    assert (declared["layer"], declared["source"], declared["unit"]) \
        == ("models/moe", "program_counter", "ratio")
    assert CELL in declared["workloads"] and "moe.act_zero_share" in cell.declared(True)
    assert "moe.act_zero_share" not in cell.declared(False)
    readers = {"moe.act_zero_share": reader}
    assert layer_metrics.read_all(readers, {"moe": {"act_zero_share": 0.4975}}) \
        == {"moe.act_zero_share": 0.4975}
    assert layer_metrics.read_all(readers, {"moe": {"act_zero_share": 0.0}}) \
        == {"moe.act_zero_share": 0.0}
    # a program whose experts count no zeroed product reports -1 from the
    # step; a run that observed nothing under the path is an error that names it
    with pytest.raises(KeyError, match="moe.act_zero_share"):
        layer_metrics.read_all(readers, {"moe": {}})


def test_the_cell_joins_the_accepted_metrics_of_its_layers(cell):
    """The shared expert's scope is not declared for it (there is none)."""
    manifest = Manifest()
    joined = {m["name"]: m["workloads"] for m in manifest.doc["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in ("train.mfu", "kernel.flash_roofline.train", "kernel.attn_win_roofline.train",
                 "kernel.attn_win_share.train", "kernel.moe_gmm_roofline.train",
                 "moe.held_share", "moe.load_max_over_mean", "scope.moe_route_share.train",
                 "scope.gqa_full_share.train", "scope.gqa_win_share.train", "attn.window_share",
                 "device.idle_share.train", "scope.unscoped_share.train"):
        assert name in joined, name
    assert "scope.moe_shared_share.train" not in joined
    assert set(cell.declared(False)) == {"train_tok_s_chip", "setup_s"}
    assert manifest.problems() == []
