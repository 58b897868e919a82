"""The configuration of Mamba-2 state-space layers beside un-roped grouped-query
attention (granite-4.0-h-micro: its first period of ten layers, a quarter of
the tied vocabulary), its counts, its readers, and the runner's limits against
the controls they are meant to refuse, at the rehearsal size on the CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_ssm, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_ssm

CELL = "granite-4.0-h-micro.train-long-ssm"
CONFIG = "granite-4.0-h-micro"
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
NEW_METRICS = ["scope.mamba_proj_share.train", "scope.mamba_conv_share.train",
               "scope.mamba_scan_share.train", "scope.mamba_out_share.train",
               "kernel.ssd_share.train", "kernel.ssd_roofline.train", "ssm.decay_mean"]


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == CONFIG)


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms and the
    skips moved as the runner moves them, and the inputs of a layer check."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-ssm.json")) as f:
        doc = json.load(f)
    cfg = train_ssm.model_config(doc["model"], doc["train"], remat_policy="attn",
                                 dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: train_ssm.seed_leaves(init_params(cfg, key), key))(key)
    n = 64
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    h, g = (jax.random.normal(k, (n, cfg.hidden), cfg.dtype) for k in keys[:2])
    pick = lambda slot: jax.tree.map(lambda a: a[0], params["layers"][slot])  # noqa: E731
    return doc, cfg, params, (h, g, train_ssm.check_operands(cfg, keys[2], n)), (
        pick("slot2"), pick("slot1"))


def test_the_configuration_keeps_every_published_number_but_the_three_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key == "layer_types":
                assert where[key] == value[:10] == PERIOD, key
            elif key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    assert set(model) == set(row["config"]) and row["config"]["layer_types"] == PERIOD * 4
    assert model["num_hidden_layers"] == 10 and model["vocab_size"] * 4 == 100352
    assert (config["num_hidden_layers_published"], config["vocab_size_published"]) == (40, 100352)
    assert config["layer_types_published"] == row["config"]["layer_types"]
    assert {k: config[k] for k in model} == model
    # what the file owes its reader: the deployment, the assumed points, the
    # memory readings of every choice of the rule with the one taken, the map
    assert "pipeline" in config["deployment"] and "25,088" in config["deployment"]
    assert {"time_step_limit", "ssm_init", "gated_norm", "conv", "in_proj", "attention", "mlp",
            "multipliers", "chunk", "weights", "optimizer"} <= set(config["assumed"])
    for choice in ("(a)", "(b)", "(c)", "(d)", "TAKEN"):
        assert choice in config["memory"], choice
    assert config["train"]["batch"] == 1 and "ssm_decoder.py" in config["files"]
    assert config["chips"] == 1


def test_the_program_is_told_the_published_widths_and_the_four_multipliers(cell):
    model = cell.config["model"]
    cfg = train_ssm.model_config(model, cell.config["train"])
    assert cfg.lead_pattern == () and cfg.n_periods == 1 and len(cfg.layer_pattern) == 10
    assert cfg.layer_pattern == ("mamba2",) * 5 + ("gqa",) + ("mamba2",) * 4
    a, b = cfg.mamba2, cfg.gqa
    assert dataclasses.asdict(a) == {"heads": 64, "head_dim": 64, "state": 128, "groups": 1,
                                     "conv": 4, "chunk": 256}
    assert (b.heads, b.kv_heads, b.head_dim, b.rope_theta, b.softmax_scale, b.window, b.gate) \
        == (32, 8, 64, 0.0, 0.015625, 0, "none")
    assert (cfg.hidden, cfg.intermediate, cfg.vocab_size, cfg.norm_eps, cfg.moe_experts) \
        == (2048, 8192, 25088, 1e-5, 0)
    assert cfg.tie_embeddings is True
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale) == (12.0, 0.22, 0.125)
    arch = train_ssm.reference_arch(model)
    assert arch["kinds"]["mamba2"] == dataclasses.asdict(a)
    assert arch["kinds"]["gqa"] == {"heads": 32, "kv_heads": 8, "head_dim": 64,
                                    "rope_theta": 0.0, "softmax_scale": 0.015625}
    assert (arch["pattern"], arch["gate_inside"]) == (cfg.layer_pattern, True)
    assert (arch["embed_scale"], arch["residual_scale"], arch["logit_scale"]) \
        == (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale)
    # the period is read off ``layer_types``; a layer type the row does not have is no kind
    assert flops_ssm.period({**model, "num_hidden_layers": 20,
                             "layer_types": model["layer_types"] * 2}) == list(cfg.layer_pattern)
    with pytest.raises(KeyError):
        flops_ssm.layer_kinds({**model, "layer_types": ["lightning-attn"] * 10})


def test_parameter_counts_and_flops_by_hand_and_equal_to_the_programs(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    mlp = 2048 * 16384 + 8192 * 2048
    mamba = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048 + mlp + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert 36 * mamba + 4 * attention + 100352 * 2048 + 2048 == 3_191_396_096
    assert "3,191,396,096" in cell.config["parameters_published_note"]
    held = 9 * mamba + attention + 25088 * 2048 + 2048
    assert held == 797_850_560 == flops_ssm.param_count(model) == cell.config["parameters"]
    assert seq == 32768
    want = {"ssm_state": 9 * 64 * 4 * 64 * 128,
            "attention_scores": 2 * 32 * 2 * 64 * (seq + 1) / 2,
            "projections": 2 * (9 * (2048 * 8512 + 4096 * 2048)
                                + 2 * 2048 * 2048 + 2 * 2048 * 512),
            "mlp": 10 * 2 * mlp, "head": 2 * 2048 * 25088}
    assert flops_ssm.forward_flops_by_part(model, seq) == pytest.approx(want, rel=1e-12)
    forward = sum(want.values())
    assert flops_ssm.train_flops_per_token(model, seq) == pytest.approx(3 * forward, rel=1e-12)
    cfg = train_ssm.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    share = {k: 100 * v / forward for k, v in want.items()}
    # the one attention layer's triangle is a twelfth of the FLOPs at 32k, the
    # scans' own products a percent; the state-space layers' products carry the rest
    assert 7 < share["attention_scores"] < 9 and share["ssm_state"] < 1.5
    assert 5.5 < share["head"] < 6.5   # 6.4% of the matmul parameters, less the triangle


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes, and both new
    kernels' operations and bytes against what they record of themselves."""
    from ray_tpu.ops import trace_log
    from ray_tpu.ops.ssd import ssd

    doc, cfg, params, (_, _, operands), layers = tiny
    model = doc["model"]
    assert flops_ssm.param_count(model) == sum(leaf.size for leaf in jax.tree.leaves(params))
    x, dt, bm, cm = (t.astype(jnp.bfloat16) if t.dtype != jnp.float32 else t for t in operands)
    rate, skip = -jnp.exp(layers[0]["a_log"]), layers[0]["d_skip"]
    jax.jit(jax.grad(lambda x: ssd(x, dt, rate, bm, cm, skip,
                                   chunk=cfg.mamba2.chunk).astype(jnp.float32).sum()))(x)
    recorded = trace_log.kernel_costs()
    want = flops_ssm.ssd_kernel_costs(model, 1, x.shape[2])
    assert sorted(want) == ["ssd_bwd", "ssd_fwd"]
    for kernel, cost in want.items():
        assert (recorded[kernel]["flops"], recorded[kernel]["bytes"]) == cost, kernel
    # at the cell's size: 128 chunks of 256, the backward pass in 8 groups of 8 heads
    big = cell.config["model"]
    assert flops_ssm.ssd_bwd_groups(flops_ssm.kinds(big)["mamba2"], 32768) == 8
    fast = flops_ssm.ssd_kernel_costs(big, 1, 32768)
    inside, state, shared = (2 * 32768 * 256 * 64 * 64, 2 * 32768 * 64 * 128 * 64,
                             2 * 32768 * 256 * 128)
    tensor, pair, rows = 32768 * 4096 * 2, 2 * 32768 * 128 * 2, 2 * 64 * 32768 * 4
    assert fast["ssd_fwd"] == (shared + inside + 2 * state, 2 * tensor + pair + rows)
    assert fast["ssd_bwd"] == (24 * shared + 2 * inside + 7 * state,
                               4 * tensor + 16 * pair + 3 * rows + 8 * 2 * 32768 * 128 * 4)
    flash = flops_ssm.flash_kernel_costs(big, 1, 32768)
    assert flash["flash_fwd"][0] == 2 * 32 * 32768 * 32768 / 2 * 2 * 64
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # ~380 FLOP a byte forward: the operations bind on a chip of 240
    assert flops_ssm.roofline_seconds(*fast["ssd_fwd"], peaks) == fast["ssd_fwd"][0] / 197e12


def test_the_runner_refuses_a_model_or_a_control_it_does_not_know(monkeypatch, cell):
    from benchmark.runners import Context

    with pytest.raises(RunFailure, match="builds no model of type"):
        train_ssm.model_config({**cell.config["model"], "model_type": "nemotron_h"},
                               cell.config["train"])
    monkeypatch.setenv("BENCH_SSM_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_SSM_CONTROL"):
        train_ssm.run(ctx)


LIMITS = train_ssm
REFUSED_BY = {
    "fp8_weights": lambda e: min(e["mamba"]["out"]["max"], e["attention"]["out"]["max"])
    > LIMITS.MIXER_RTOL,
    "no_skip": lambda e: e["mamba"]["out"]["max"] > LIMITS.MIXER_RTOL
    and e["recurrence"]["all"] > LIMITS.STATE_RTOL,
    "no_dt_bias": lambda e: e["mamba"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "sqrt_scale": lambda e: e["attention"]["out"]["max"] > LIMITS.MIXER_RTOL,
    "bf16_state": lambda e: e["recurrence"]["all"] > LIMITS.STATE_RTOL,
    "gate_after_norm": lambda e: e["mamba"]["out"]["max"] > LIMITS.MIXER_RTOL,
}


def _layer_readings(tiny, control):
    doc, _, _, (h, g, operands), ref_layers = tiny
    cfg = train_ssm.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                 dtype=jnp.float32)
    layers = ref_layers
    if control == "fp8_weights":
        layers = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                              if a.ndim > 1 else a, layers)
    planted = train_ssm.planted({"layers": {"slot0": layers[0]}}, control)
    return train_ssm.layer_errors(
        cfg, train_ssm.reference_arch(doc["model"], control),
        (planted["layers"]["slot0"], layers[1]), ref_layers, h, g, operands, control)


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # ``residual_1`` leaves every layer as it is and changes the stack (the
    # model's tests show the multiplier to matter), ``reference_default_precision``
    # is the backend's own float32 on a CPU, and the last two change the
    # compared step: tests/test_gqa_window_model.py puts them through the
    # ``step_errors`` this runner imports
    assert set(REFUSED_BY) | {"residual_1", "reference_default_precision", "half_batch",
                              "unchanged_state"} == set(train_ssm.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert e["recurrence"]["all"] < 1e-5
    assert max(e[kind][what]["max"] for kind in ("mamba", "attention")
               for what in ("out", "grad")) < 1e-4
    assert not any(refuses(e) for refuses in REFUSED_BY.values())


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)`` on
    ``planted`` leaves: the controls of the program change those, and nothing
    else of it."""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_ssm.model_config(model, sizes)
    changed = {}
    for control in train_ssm.CONTROLS:
        cfg = train_ssm.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {**{c: set() for c in train_ssm.CONTROLS},
                       "sqrt_scale": {"gqa"}, "residual_1": {"residual_scale"}}
    assert train_ssm.model_config(model, sizes, "sqrt_scale").gqa == dataclasses.replace(
        true.gqa, softmax_scale=None)
    assert train_ssm.model_config(model, sizes, "residual_1").residual_scale == 1.0
    leaves = {"layers": {"slot0": {"d_skip": jnp.ones(4), "dt_bias": jnp.ones(4)},
                         "slot5": {"wq": jnp.ones(4)}}}
    zeroed = lambda control: {  # noqa: E731
        (slot, k) for slot, layer in train_ssm.planted(leaves, control)["layers"].items()
        for k, v in layer.items() if not v.any()}
    assert zeroed("no_skip") == {("slot0", "d_skip")}
    assert zeroed("no_dt_bias") == {("slot0", "dt_bias")}
    assert zeroed(None) == zeroed("fp8_weights") == set()
    assert train_ssm.reference_arch(model, "gate_after_norm")["gate_inside"] is False


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_kernels(cell):
    tail = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    scope = lambda path: (  # noqa: E731
        f', frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}')
    kernels = ["ssd_fwd", "ssd_bwd"]
    ops = {f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
           + scope("stack/attn/mamba_scan"): [1.0, 2] for i, n in enumerate(kernels)}
    for i, (name, seconds) in enumerate((("mamba_proj", 4.0), ("mamba_conv", 3.0),
                                         ("mamba_scan", 1.0), ("mamba_out", 2.0))):
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
    assert {declared[m]["layer"] for m in NEW_METRICS} == {"models/mamba2", "ops/ kernels"}

    def read(ops, seconds):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "ssm": {"decay_mean": 0.61},
               "ssd": {"least_seconds": seconds[0], "seconds": seconds[1]}}
        return layer_metrics.read_all(readers, obs)

    assert read(ops, (0.5, 2.0)) == {
        "scope.mamba_proj_share.train": 20.0, "scope.mamba_conv_share.train": 15.0,
        "scope.mamba_scan_share.train": 15.0, "scope.mamba_out_share.train": 10.0,
        "kernel.ssd_share.train": 10.0, "kernel.ssd_roofline.train": 25.0,
        "ssm.decay_mean": 0.61}
    # no such scope and no such call (a CPU rehearsal; an older program): the
    # runner hands the window's seconds for the calls' own, and each reads 0
    bare = {"%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
            + scope("stack/attn"): [1.0, 1]}
    none = read(bare, (0.0, 25.0))
    assert [none[m] for m in NEW_METRICS[:6]] == [0.0] * 6
    for kernel in kernels:
        own = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
        assert trace_reduce.matching(ops, own) == (1.0, 2)
    # an op that only MENTIONS a scope (an operand's name) is not in it
    mention = {"%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %mamba_scan.1), kind=kLoop": [1.0, 1]}
    assert trace_reduce.matching(
        mention, readers["scope.mamba_scan_share.train"]["params"]["pattern"]) == (0.0, 0)


def test_the_cell_joins_the_accepted_metrics_that_are_true_of_it(cell):
    """The step's own metrics, the top-level scopes and the three plain flash
    kernels' shares (its attention layer calls them); no other configuration's
    kernels or scopes, and not the six ``pass.*`` lists, which a green test
    holds to the eight older cells."""
    manifest = Manifest()
    joined = {m["name"] for m in manifest.doc["per_layer"] if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "train.mfu", "train.step_ms", "train.data_wait_ms", "train.report_ms",
        "device.idle_share.train", "kernel.custom_call_share.train",
        *(f"kernel.flash_{part}_share.train" for part in ("fwd", "bwd_dq", "bwd_dkdv")),
        *(f"scope.{s}_share.train" for s in (
            "attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped"))}
    assert set(cell.declared(False)) == {"train_tok_s_chip", "setup_s"}
    assert manifest.doc["workloads"][-1]["name"] == CELL
    assert manifest.doc["configs"][-1]["name"] == CONFIG
    assert manifest.problems() == []
