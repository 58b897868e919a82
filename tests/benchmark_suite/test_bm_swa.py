"""The configuration of full and window layers of grouped-query attention
with a head count a kind (Laguna-S-2.1: its first five layers, a chip's share
of the experts and of the vocabulary), its counts, and the runner's limits
against the controls they are meant to refuse, at the rehearsal size on the
CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_swa, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_swa

CELL = "laguna-s-2.1.train-16k-swa"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"]
NEW_METRICS = ["scope.gqa_full_share.train", "scope.gqa_win_share.train",
               "kernel.flash_roofline.train", "attn.window_share"]
LISTS = ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer")


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Laguna-S-2.1")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms moved
    as the runner moves them, and one layer-check input."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-swa.json")) as f:
        doc = json.load(f)
    cfg = train_swa.model_config(doc["model"], doc["train"], remat_policy="attn",
                                 dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: train_swa.seed_norms(init_params(cfg, key), key))(key)
    h = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.hidden), cfg.dtype)
    pick = lambda slot: jax.tree.map(lambda a: a[0], params["layers"][slot])  # noqa: E731
    return doc, cfg, params, h, (pick(f"slot{cfg.layer_pattern.index('gqa')}"),
                                 pick(f"slot{cfg.layer_pattern.index('gqa_win')}"))


def test_the_configuration_keeps_every_published_number_but_the_seven_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key in LISTS:
                assert where[key] == value[:5], key
            elif key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    assert (model["num_hidden_layers"], model["num_experts"], model["vocab_size"]) \
        == (5, 32, 12544)
    # the leading dense layer and a whole period after it, at least 8 experts,
    # an eighth of the vocabulary: the guide's floors
    assert model["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert model["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert model["vocab_size"] * 8 == 100352 and model["num_experts"] * 8 == 256
    assert set(model) - set(row["config"]) == {"num_experts_published", "router_width",
                                               "experts_held"}
    assert (model["router_width"], model["experts_held"], model["num_experts_published"]) \
        == (256, [0, 31], 256)
    # what the file owes its reader: the deployment, the count, the assumed
    # points, the memory readings with the choice, the map of the files
    assert "8 chips share each layer" in config["deployment"]
    assert config["parameters"] == 1_716_986_880 and len(config["assumed"]) >= 5
    assert "1 row" in config["memory"] and "2 rows" in config["memory"]
    assert config["train"]["batch"] in (1, 2) and "windowed_moe_decoder.py" in config["files"]


def test_the_program_is_told_the_published_widths_and_the_share(cell):
    cfg = train_swa.model_config(cell.config["model"], cell.config["train"])
    assert cfg.lead_pattern == ("gqa",) and cfg.n_periods == 1
    assert cfg.layer_pattern == ("gqa_win", "gqa_win", "gqa_win", "gqa")
    yarn = dataclasses.astuple(cfg.gqa.yarn)
    assert yarn == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert dataclasses.astuple(cfg.gqa)[:5] + dataclasses.astuple(cfg.gqa)[6:] \
        == (48, 8, 128, 5e5, 64, 0, "headwise")
    assert dataclasses.astuple(cfg.gqa_window) == (72, 8, 128, 1e4, 0, None, 512, "headwise")
    assert (cfg.hidden, cfg.lead_intermediate, cfg.intermediate, cfg.moe_shared) \
        == (3072, 12288, 1024, 1024)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_routed_scale) \
        == (256, 10, (0, 32), "softmax", 2.5)
    assert cfg.moe_norm_topk and not cfg.moe_shared_gate and not cfg.norm_plus_one
    assert (cfg.vocab_size, cfg.moe_aux_weight, cfg.moe_z_weight, cfg.norm_eps) \
        == (12544, 0.001, 0.0, 1e-6)
    arch = train_swa.reference_arch(cell.config["model"])
    assert arch["kinds"]["gqa"] == {**dataclasses.asdict(cfg.gqa)}
    assert arch["kinds"]["gqa_win"] == dataclasses.asdict(cfg.gqa_window)
    assert (arch["top_k"], arch["held_first"], arch["routed_scale"]) == (10, 0, 2.5)
    # the period is read off ``layer_types``: the shortest unit that repeats
    model = cell.config["model"]
    assert arch["pattern"] == cfg.layer_pattern == tuple(flops_swa.period(model))
    twice = {**model, "layer_types": model["layer_types"] + model["layer_types"][1:],
             "mlp_layer_types": model["mlp_layer_types"] + ["sparse"] * 4}
    assert flops_swa.period(twice) == flops_swa.period(model)
    uneven = {**twice, "layer_types": twice["layer_types"][:-1] + ["sliding_attention"]}
    assert len(flops_swa.period(uneven)) == 8


def test_parameter_counts_by_hand(cell):
    model = cell.config["model"]
    full = 3072 * 128 * (2 * 48 + 2 * 8) + 3072 * 48
    window = 3072 * 128 * (2 * 72 + 2 * 8) + 3072 * 72
    expert, dense = 3 * 3072 * 1024, 3 * 3072 * 12288
    assert (full, window, expert, dense) == (44_187_648, 63_135_744, 9_437_184, 113_246_208)
    experts = 3072 * 256 + 33 * expert                 # router, shared + 32 held
    total = (full + dense + 2 * 3072 + full + experts + 2 * 3072
             + 3 * (window + experts + 2 * 3072) + 2 * 3072 * 12544 + 3072)
    assert flops_swa.param_count(model) == total == cell.config["parameters"] == 1_716_986_880
    # every width as published: 48 layers, 256 experts, 100,352 words
    whole = (12 * full + 36 * window + dense + 47 * (3072 * 256 + 257 * expert)
             + 48 * 2 * 3072 + 2 * 3072 * 100352 + 3072)
    assert whole == pytest.approx(117.56e9, rel=1e-4)


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    assert seq == 16384
    band = (512 * 513 / 2 + (seq - 512) * 512) / seq          # keys a query keeps
    assert flops_swa.window_share(seq, 512) == pytest.approx(
        (512 * seq - 512 * 511 / 2) / (seq * (seq + 1) / 2), rel=1e-12)
    assert flops_swa.window_share(seq, 512) == pytest.approx(0.0615, abs=5e-5)
    want = {"full_scores": 2 * 2 * 48 * 256 * (seq + 1) / 2,
            "window_scores": 3 * 2 * 72 * 256 * band,
            "projections": 2 * (2 * 44_187_648 + 3 * 63_135_744),
            "dense_mlp": 2 * 113_246_208,
            "experts": 4 * 2 * (3072 * 256 + 3 * 3072 * 1024 + 10 * (32 / 256) * 3 * 3072 * 1024),
            "head": 2 * 3072 * 12544}
    parts = flops_swa.forward_flops_by_part(model, seq)
    assert parts == pytest.approx(want, rel=1e-12)
    forward = sum(want.values())
    assert forward == pytest.approx(1.4937e9, rel=1e-4)
    share = {k: round(100 * v / forward) for k, v in want.items()}
    assert share == {"full_scores": 27, "window_scores": 4, "projections": 37, "dense_mlp": 15,
                     "experts": 12, "head": 5}
    assert flops_swa.train_flops_per_token(model, seq) == pytest.approx(3 * forward, rel=1e-12)
    cfg = train_swa.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    # at 8k the full layers' scores are 16%
    at_8k = flops_swa.forward_flops_by_part(model, 8192)
    assert round(100 * at_8k["full_scores"] / sum(at_8k.values())) == 16


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes, and every
    attention kernel's operations and bytes against what it records of itself."""
    from ray_tpu.models.gqa import gqa_mixer
    from ray_tpu.ops import trace_log

    doc, cfg, params, h, layers = tiny
    model = doc["model"]
    assert flops_swa.param_count(model) == sum(leaf.size for leaf in jax.tree.leaves(params))
    seq = h.shape[0]

    def loss(layer, spec):
        return gqa_mixer(h[None], layer, spec, config=cfg, positions=jnp.arange(seq))[0].sum()

    jax.jit(jax.grad(loss), static_argnums=1)(layers[0], cfg.gqa)
    jax.jit(jax.grad(loss), static_argnums=1)(layers[1], cfg.gqa_window)
    recorded = trace_log.kernel_costs()
    want = {**flops_swa.attention_kernel_costs(model, "gqa", 1, seq),
            **flops_swa.attention_kernel_costs(model, "gqa_win", 1, seq)}
    assert sorted(want) == ["attn_win_bwd_dkdv", "attn_win_bwd_dq", "attn_win_fwd",
                            "flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]
    for kernel, (kernel_flops, kernel_bytes) in want.items():
        got = recorded[kernel]
        # the rehearsal computes in float32: 4-byte operands where the count has bf16's 2
        assert got["flops"] == pytest.approx(kernel_flops, rel=1e-12), kernel
        assert kernel_bytes <= got["bytes"] <= 2 * kernel_bytes, kernel
    # at the cell's size: the band's pairs at 72 heads, half the square at 48
    big = cell.config["model"]
    win = flops_swa.attention_kernel_costs(big, "gqa_win", 1, 16384)
    full = flops_swa.attention_kernel_costs(big, "gqa", 1, 16384)
    assert win["attn_win_fwd"][0] == 2 * 2 * 72 * (512 * 513 / 2 + 15872 * 512) * 128
    assert full["flash_fwd"][0] == 2 * 2 * 48 * 16384 * 16384 / 2 * 128
    assert full["flash_bwd_dkdv"][0] == 2 * full["flash_fwd"][0]
    # dK and dV leave the backward kernel at the QUERY heads' count: 302 MB each
    # a window layer at 72 heads where the keys hold a ninth
    q_b, kv_b, stats = 72 * 16384 * 128 * 2, 2 * 8 * 16384 * 128 * 2, 72 * 16384 * 4
    assert win["attn_win_bwd_dkdv"][1] == 2 * q_b + kv_b + 2 * stats + 2 * q_b
    assert q_b == 301_989_888
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops_swa.roofline_seconds(*full["flash_fwd"], peaks) == full["flash_fwd"][0] / 197e12


def test_the_runner_refuses_a_model_type_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model of type"):
        train_swa.model_config({**cell.config["model"], "model_type": "dots3_note"},
                               cell.config["train"])


REFUSED_BY = {
    "fp8_weights": lambda e: min(e["full"]["max"], e["window"]["max"], e["experts"]["max"])
    > train_swa.MIXER_RTOL,
    "no_window": lambda e: e["window"]["max"] > train_swa.MIXER_RTOL
    and abs(e["window"]["window_share"] - e["share"]) > train_swa.WINDOW_SHARE_ATOL,
    "plain_rope": lambda e: e["full"]["max"] > train_swa.MIXER_RTOL,
    "no_gate": lambda e: min(e["full"]["max"], e["window"]["max"]) > train_swa.MIXER_RTOL,
    "no_scale": lambda e: e["experts"]["max"] > train_swa.LAYER_RTOL,
    "win_48_heads": lambda e: e["window"]["max"] > train_swa.MIXER_RTOL,
    "softmax_held": lambda e: e["experts"]["max"] > train_swa.LAYER_RTOL
    and e["experts"]["held_share"] == 1.0,
}


def _layer_readings(tiny, control):
    doc, _, _, h, ref_layers = tiny
    cfg = train_swa.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                 dtype=jnp.float32)
    layers = ref_layers
    if control == "fp8_weights":
        layers = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), layers)
    out = train_swa.layer_errors(cfg, train_swa.reference_arch(doc["model"]), layers,
                                 ref_layers, h, control)
    out["share"] = flops_swa.window_share(h.shape[0], doc["model"]["sliding_window"])
    return out


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # the two others leave every layer as it is and change the compared step:
    # tests/test_gqa_window_model.py puts them through ``step_errors``
    assert set(REFUSED_BY) | {"half_batch", "unchanged_state"} == set(train_swa.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert max(e["full"]["max"], e["window"]["max"], e["experts"]["max"]) < 1e-4
    assert abs(e["window"]["window_share"] - e["share"]) < 1e-6
    assert not any(refuses(e) for refuses in REFUSED_BY.values())
    assert e["experts"]["dropped"] == 0 and e["experts"]["rows"] == 128 * 3


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_kernels():
    tail = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    scope = lambda path: f', frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}'  # noqa: E731
    flash = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"]
    win = ["attn_win_fwd", "attn_win_bwd_dq", "attn_win_bwd_dkdv"]
    ops = {f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
           + scope("stack/attn/gqa_full"): [1.0, 2] for i, n in enumerate(flash)}
    ops.update({f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
                + scope("stack/attn/gqa_win"): [1.0, 2] for i, n in enumerate(win)})
    ops["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/attn/gqa_win/attn_gate")] = [2.0, 4]
    ops["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" + scope("stack/attn")] = [1.0, 1]
    ops["%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/mlp/moe_experts")] = [1.0, 1]
    manifest = Manifest()
    readers = {m: json.load(open(manifest.reader_file(m))) for m in NEW_METRICS}
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, reader in readers.items():
        assert declared[name]["workloads"] == [CELL]
        for k in ("layer", "unit", "moves"):
            assert reader[k] == declared[name][k]
    assert {declared[m]["layer"] for m in NEW_METRICS} == {"models/gqa", "ops/ kernels"}

    def read(ops, flash_seconds):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "attn": {"window_share": 0.0615},
               "flash": {"least_seconds": flash_seconds[0], "seconds": flash_seconds[1]}}
        return layer_metrics.read_all(readers, obs)

    assert read(ops, (1.5, 3.0)) == {
        "scope.gqa_full_share.train": 15.0, "scope.gqa_win_share.train": 25.0,
        "kernel.flash_roofline.train": 50.0, "attn.window_share": 0.0615}
    # no such scope and no such call (a CPU rehearsal; an older program): the
    # runner hands the window's seconds for the calls' own, and each reads 0
    bare = {"%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
            + scope("stack/attn"): [1.0, 1]}
    none = read(bare, (0.0, 25.0))
    assert [none[m] for m in NEW_METRICS[:3]] == [0.0] * 3
    for kernel in flash + win:
        own = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
        assert trace_reduce.matching(ops, own) == (1.0, 2)
    # the accepted metrics this cell joins list it, last
    joined = [m["name"] for m in manifest.doc["per_layer"] if CELL in m.get("workloads", ())]
    assert len(joined) == 29 and set(NEW_METRICS) <= set(joined)
    assert all(m["workloads"][-1] == CELL for m in manifest.doc["per_layer"]
               if CELL in m.get("workloads", ()))


SCOPE_METRICS = {
    *(f"scope.{s}_share.train" for s in (
        "attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped",
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
        "gqa_full", "gqa_win"))}


def test_the_cell_declares_its_scope_metrics_in_the_traced_run_only(cell):
    """The six top-level scopes, the five of the expert layer and the two this
    PR brings; no other configuration's (test_bm_scopes.py holds the five
    older cells to theirs; its own count of the LAST rows is a `benchmark`
    issue's to mend, PERF.md section 7)."""
    assert SCOPE_METRICS == {m for m in cell.declared(True) if m.startswith("scope.")}
    assert not [m for m in cell.declared(False) if m.startswith("scope.")]


@pytest.mark.parametrize("scope", ["gqa_full", "gqa_win"])
def test_a_kinds_scope_reader_matches_its_own_names_and_no_other_scopes(scope):
    with open(Manifest().reader_file(f"scope.{scope}_share.train")) as f:
        r = json.load(f)
    assert (r["kind"], r["params"]["of"]) == ("trace_share", "busy_s")
    hits = lambda names: trace_reduce.matching(  # noqa: E731
        {n: [1.0, 1] for n in names}, r["params"]["pattern"])[1]
    op = lambda i, path: (  # noqa: E731
        f"%fusion.{i} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p.{i}), kind=kLoop, calls=%fc.{i}"
        + (f', frontend_attributes={{rt_scope="{path}"}}' if path else ""))
    # its `what` quotes an event name of the real trace, which its pattern matches
    assert hits([r["what"].split("(PR 39): ")[1]]) == 1
    other = {"gqa_full": "gqa_win", "gqa_win": "gqa_full"}[scope]
    own = [scope, f"stack/attn/{scope}", f"stack/attn/{scope}/attn_gate"]
    others = [f"stack/attn/{other}", f"stack/attn/{other}/attn_gate", f"stack/attn/{scope}_x",
              f"stack/attn/x{scope}", "stack/attn", "stack/mlp/moe_experts", ""]
    assert hits([op(i, p) for i, p in enumerate(own)]) == len(own)
    assert hits([op(i, p) for i, p in enumerate(others)]) == 0
    # an op that only MENTIONS the scope (an operand's name) is not in it
    assert hits([f"%fusion.9 = bf16[8]{{0}} fusion(bf16[8]{{0}} %{scope}.1), kind=kLoop"]) == 0


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)``:
    the five controls that keep the leaves change that config (the two that
    reshape leaves stand in ``layer_errors`` alone, above), and nothing else
    of it. (The uncontrolled rehearsal through the harness, both trace modes,
    is test_bm_rehearsal.py's; the controls through the harness ran on the
    chip, PERF.md section 6.)"""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_swa.model_config(model, sizes)
    changed = {}
    for control in train_swa.CONTROLS:
        cfg = train_swa.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {"fp8_weights": set(), "no_window": {"gqa_window"},
                       "plain_rope": {"gqa"}, "no_gate": {"gqa", "gqa_window"},
                       "no_scale": {"moe_routed_scale"}, "win_48_heads": set(),
                       "softmax_held": set(), "half_batch": set(), "unchanged_state": set()}
    assert train_swa.model_config(model, sizes, "no_window").gqa_window.window >= 16384
    assert train_swa.model_config(model, sizes, "plain_rope").gqa == dataclasses.replace(
        true.gqa, yarn=None)
    assert train_swa.model_config(model, sizes, "no_scale").moe_routed_scale == 1.0
    assert set(train_swa.LAYER_CONTROLS) == {"win_48_heads", "softmax_held"}


def test_an_unknown_control_is_refused_before_a_cluster_starts(monkeypatch, cell):
    from benchmark.runners import Context

    monkeypatch.setenv("BENCH_SWA_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_SWA_CONTROL"):
        train_swa.run(ctx)
