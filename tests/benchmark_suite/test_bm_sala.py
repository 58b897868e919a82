"""The configuration of block-selected sparse attention beside lightning linear
attention (MiniCPM-SALA: its first period of one block-selected layer and three
lightning layers, a quarter of the vocabulary), its counts, its readers, and
the runner's limits against the controls they are meant to refuse, at the
rehearsal size on the CPU."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_sala, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_sala
from benchmark.runners.train_hybrid import seed_norms

CELL = "minicpm-sala.train-16k-sala"
CONFIG = "minicpm-sala"
REDUCED = ["num_hidden_layers", "mixer_types", "vocab_size"]
ADDED = {"sparse_config", "layer_ids", "num_hidden_layers_published"}
NEW_METRICS = ["kernel.lightning_share.train", "kernel.attn_blk_share.train",
               "scope.lightning_scan_share.train", "scope.sparse_select_share.train",
               "scope.sparse_attn_share.train", "kernel.lightning_roofline.train",
               "kernel.attn_blk_roofline.train", "attn.block_kept_share"]


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms moved as
    the runner moves them, and the inputs of a layer check."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-sala.json")) as f:
        doc = json.load(f)
    cfg = train_sala.model_config(doc["model"], doc["train"], remat_policy="attn",
                                  dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda key: seed_norms(init_params(cfg, key), key))(key)
    n = doc["check_tokens"]
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    h, g = (jax.random.normal(k, (n, cfg.hidden), cfg.dtype) for k in keys[:2])
    qkv = [jax.random.normal(k, (1, cfg.lightning.heads, n, cfg.lightning.head_dim),
                             jnp.bfloat16) for k in keys[2:]]
    pick = lambda slot: jax.tree.map(lambda a: a[0], params["layers"][slot])  # noqa: E731
    return doc, cfg, params, (h, g, qkv), (pick("slot0"), pick("slot1"))


def test_the_configuration_keeps_every_published_number_but_the_three_cut(cell):
    config, row = cell.config, catalog()
    model = config["model"]
    assert config["reduced"] == REDUCED and config["source"] == row["source_url"]
    entry = next(c for c in Manifest().doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == row["source_url"]
    depth = model["num_hidden_layers"]
    for key, value in row["config"].items():
        for where in (model, config):  # the program's group, and the contract's top level
            if key == "mixer_types":
                assert where[key] == [value[i] for i in model["layer_ids"]], key
            elif key in REDUCED:
                assert where[key] < value, key
            else:
                assert where[key] == value and type(where[key]) is type(value), key
    # whole periods of (minicpm4, lightning x 3) with the layers' published
    # indices; a quarter of the vocabulary
    assert depth in (4, 8) and model["mixer_types"] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"] * (depth // 4)
    assert model["layer_ids"] == [0, 1, 2, 3, 9, 10, 11, 12][:depth]
    assert model["vocab_size"] * 4 == 73448 == config["vocab_size_published"]
    assert config["num_hidden_layers_published"] == 32 == len(config["mixer_types_published"])
    assert config["mixer_types_published"] == row["config"]["mixer_types"]
    assert set(model) - set(row["config"]) == ADDED
    assert model["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                      "init_blocks": 1, "window_size": 2048, "topk": 64,
                                      "dense_len": 8192}
    assert {k: config[k] for k in model if k not in ADDED - {"num_hidden_layers_published"}} \
        == {k: v for k, v in model.items() if k not in ADDED - {"num_hidden_layers_published"}}
    # what the file owes its reader: the deployment, the assumed points, the
    # memory readings of every choice of the rule with the one taken, the map
    assert "vocabulary-parallel" in config["deployment"] and "18,362" in config["deployment"]
    assert {"sparse_config", "pooling", "dense_len", "decay", "lightning", "sparse_layer",
            "multipliers", "rope_pairs", "weights", "optimizer"} <= set(config["assumed"])
    assert "mup_denominator" in config["assumed"]["multipliers"]
    for choice in ("(a)", "(b)", "(c)"):
        assert choice in config["memory"], choice
    assert config["train"]["batch"] == 1 and "sparse_linear_decoder.py" in config["files"]
    assert config["chips"] == 1


def test_the_program_is_told_the_published_widths_the_indices_and_the_multipliers(cell):
    model = cell.config["model"]
    cfg = train_sala.model_config(model, cell.config["train"])
    assert cfg.lead_pattern == () and cfg.n_periods == model["num_hidden_layers"] // 4
    assert cfg.layer_pattern == ("block_sparse", "lightning", "lightning", "lightning")
    a, b = cfg.block_sparse, cfg.lightning
    assert (a.heads, a.kv_heads, a.head_dim) == (32, 2, 128)
    assert a.sizes == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                       "init_blocks": 1, "window_size": 2048, "topk": 64}
    assert (b.heads, b.head_dim, b.rope_theta, b.depth) == (32, 128, 1e4, 32)
    assert (cfg.hidden, cfg.intermediate, cfg.vocab_size, cfg.norm_eps, cfg.moe_experts) \
        == (4096, 16384, 18362, 1e-6, 0)
    assert cfg.layer_ids == tuple(model["layer_ids"])
    assert (cfg.embed_scale, cfg.logit_scale) == (12.0, 1 / 16)
    assert cfg.residual_scale == pytest.approx(1.4 / math.sqrt(32), rel=1e-12)
    arch = train_sala.reference_arch(model)
    assert arch["kinds"]["lightning"] == {"heads": 32, "head_dim": 128, "rope_theta": 1e4,
                                          "depth": 32}
    assert arch["kinds"]["block_sparse"] == {"heads": 32, "kv_heads": 2, "head_dim": 128,
                                             **a.sizes}
    assert (arch["pattern"], arch["layer_ids"]) == (cfg.layer_pattern, cfg.layer_ids)
    assert (arch["embed_scale"], arch["residual_scale"], arch["logit_scale"]) \
        == (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale)
    # the period is read off ``mixer_types``; a mixer type the row does not have is no kind
    assert flops_sala.period({**model, "num_hidden_layers": 8,
                              "mixer_types": model["mixer_types"][:4] * 2}) == list(
        cfg.layer_pattern)
    with pytest.raises(KeyError):
        flops_sala.layer_kinds({**model, "mixer_types": ["mamba2"] * len(model["mixer_types"])})


def test_parameter_counts_by_hand(cell):
    model = cell.config["model"]
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert (sparse, lightning) == (253_755_392, 285_212_672)
    assert 8 * sparse + 24 * lightning + 2 * 73448 * 4096 == 9_476_833_280
    assert "9,476,833,280" in cell.config["parameters_published_note"]
    periods = model["num_hidden_layers"] // 4
    matmul = periods * (sparse + 3 * lightning) + 2 * 18362 * 4096
    assert matmul == {1: 1_259_814_912, 2: 2_369_208_320}[periods]
    norms = periods * (4 * 2 * 4096 + 4 * 2 * 128 + 3 * 4096) + 4096
    assert flops_sala.param_count(model) == matmul + norms == cell.config["parameters"]


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    assert seq == 16384 > model["sparse_config"]["dense_len"]
    periods = model["num_hidden_layers"] // 4
    # a query keeps 64 blocks of 64 keys (all it sees up to 4,096), of its own
    # block the keys up to itself
    kept = (4096 * 4097 / 2 + (seq - 4096) * 4096 - (seq - 4096) * 31.5) / seq
    assert flops_sala.kept_keys(model, seq) == pytest.approx(kept, rel=1e-12) \
        == pytest.approx(3560.5, abs=0.01)
    assert flops_sala.kept_share(model, seq) == pytest.approx(0.4346, abs=5e-5)
    assert flops_sala.kept_share(model, 4096) == 1.0
    assert flops_sala.forced_share(model, 64) == 1.0
    # at 16k a late query's 64 blocks hold the first and the 32 or 33 of the window
    assert 0.5 < flops_sala.forced_share(model, seq) < 0.62
    want = {"sparse_scores": periods * 2 * 32 * 128 * 2 * kept,
            "sparse_selection": periods * 2 * 32 * 128 * seq / 16 / 2,
            "lightning_state": 3 * periods * 32 * 4 * 128 * 128,
            "projections": periods * 2 * (3 * 4096 * 4096 + 2 * 4096 * 256
                                          + 3 * 5 * 4096 * 4096),
            "mlp": 4 * periods * 2 * 3 * 4096 * 16384,
            "head": 2 * 4096 * 18362}
    parts = flops_sala.forward_flops_by_part(model, seq)
    assert parts == pytest.approx(want, rel=1e-12)
    forward = sum(want.values())
    assert flops_sala.train_flops_per_token(model, seq) == pytest.approx(3 * forward, rel=1e-12)
    cfg = train_sala.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    share = {k: 100 * v / forward for k, v in want.items()}
    # the distinctive mixers are a few percent of the FLOPs at any length
    assert share["sparse_scores"] + share["sparse_selection"] + share["lightning_state"] < 3.5
    assert share["mlp"] > 60


def test_the_counts_are_the_programs_own(tiny, cell):
    """``param_count`` against the leaves ``init_params`` makes (the decays are
    no parameters), and every new kernel's operations and bytes against what
    it records of itself."""
    from ray_tpu.models.block_sparse import block_sparse_mixer
    from ray_tpu.models.lightning import lightning_mixer
    from ray_tpu.ops import trace_log

    doc, cfg, params, (h, _, _), layers = tiny
    model = doc["model"]
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert flops_sala.param_count(model) == sum(
        leaf.size for path, leaf in leaves if "log_decay" not in jax.tree_util.keystr(path))
    seq = h.shape[0]
    positions = jnp.arange(seq, dtype=jnp.int32)
    jax.jit(jax.grad(lambda w: block_sparse_mixer(
        h[None], w, config=cfg, positions=positions)[0].sum()))(layers[0])
    jax.jit(jax.grad(lambda w: lightning_mixer(
        h[None], w, config=cfg, positions=positions).sum()))(layers[1])
    recorded = trace_log.kernel_costs()
    want = {**flops_sala.lightning_kernel_costs(model, 1, seq),
            **flops_sala.select_kernel_costs(model, 1, seq)}
    assert sorted(want) == ["attn_blk_bwd_dkdv", "attn_blk_bwd_dq", "attn_blk_fwd",
                            "lightning_bwd", "lightning_fwd"]
    for kernel, (kernel_flops, kernel_bytes) in want.items():
        got = recorded[kernel]
        # the rehearsal computes in float32: 4-byte operands where the count has bf16's 2
        assert got["flops"] == pytest.approx(kernel_flops, rel=1e-12), kernel
        assert kernel_bytes <= got["bytes"] <= 2 * kernel_bytes, kernel
    # at the cell's size: 136 tiles of 1024 x 1024 a head, each whole, and the
    # 128-lane product that spreads its flags; four tensors a lightning pass
    big = cell.config["model"]
    blk = flops_sala.select_kernel_costs(big, 1, 16384)
    pairs = 32 * 136 * 1024 * 1024
    assert blk["attn_blk_fwd"][0] == 2 * pairs * (2 * 128 + 128)
    assert blk["attn_blk_bwd_dkdv"][0] == 2 * pairs * (4 * 128 + 128)
    fast = flops_sala.lightning_kernel_costs(big, 1, 16384)
    tensor = 32 * 16384 * 128 * 2
    assert fast["lightning_fwd"] == (4 * 2 * 32 * 16384 * 128 * 128, 4 * tensor)
    assert fast["lightning_bwd"] == (10 * 2 * 32 * 16384 * 128 * 128, 10 * tensor)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 128 FLOP a byte forward: the bytes bind on a chip of 240
    assert flops_sala.roofline_seconds(*fast["lightning_fwd"], peaks) \
        == fast["lightning_fwd"][1] / 819e9


def test_the_runner_refuses_a_model_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model of type"):
        train_sala.model_config({**cell.config["model"], "model_type": "nemotron_h"},
                                cell.config["train"])


LIMITS = train_sala
REFUSED_BY = {
    "fp8_weights": lambda e: min(e["lightning"]["out"]["max"], e["sparse"]["out"]["max"])
    > LIMITS.MIXER_RTOL,
    "no_layer_factor": lambda e: e["lightning"]["out"]["max"] > LIMITS.MIXER_RTOL
    and e["recurrence"]["all"] > LIMITS.STATE_RTOL,
    "no_local_blocks": lambda e: e["sparse"]["agree"]["sets"] < LIMITS.SETS_AGREE_MIN
    and abs(e["sparse"]["block_forced_share"] - e["forced"]) > LIMITS.SHARE_ATOL,
    "bf16_state": lambda e: e["recurrence"]["all"] > LIMITS.STATE_RTOL,
}


def _layer_readings(tiny, control):
    doc, _, _, (h, g, qkv), ref_layers = tiny
    cfg = train_sala.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                  dtype=jnp.float32)
    layers = ref_layers
    if control == "fp8_weights":
        layers = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                              if a.ndim > 1 else a, layers)
    if control == "no_layer_factor":
        from ray_tpu.models.lightning import log_decays

        layers = (layers[0], {**layers[1], "log_decay": jnp.asarray(
            log_decays(cfg.lightning, [doc["model"]["layer_ids"][1]])[0])})
    out = train_sala.layer_errors(cfg, train_sala.reference_arch(doc["model"]), layers,
                                  ref_layers, doc["model"]["layer_ids"][1], h, g, qkv, control)
    out["forced"] = flops_sala.forced_share(doc["model"], h.shape[0])
    out["kept"] = flops_sala.kept_share(doc["model"], h.shape[0])
    return out


@pytest.mark.parametrize("control", [None, *REFUSED_BY], ids=lambda c: c or "uncontrolled")
def test_the_layers_read_far_under_every_limit_and_each_control_is_refused_by_its_own(
        tiny, control):
    # ``scale_depth_1`` leaves every layer as it is and changes the stack (the
    # model's tests show the multiplier to matter), ``reference_default_precision``
    # is the backend's own float32 on a CPU, and the last two change the
    # compared step: tests/test_gqa_window_model.py puts them through the
    # ``step_errors`` this runner imports
    assert set(REFUSED_BY) | {"scale_depth_1", "reference_default_precision", "half_batch",
                              "unchanged_state"} == set(train_sala.CONTROLS)
    e = _layer_readings(tiny, control)
    if control:
        assert REFUSED_BY[control](e), (control, e)
        return
    assert e["recurrence"]["all"] < 1e-5
    assert max(e[kind][what]["max"] for kind in ("lightning", "sparse")
               for what in ("out", "grad")) < 1e-4
    assert e["sparse"]["agree"] == {"sets": 1.0, "flags": 1.0}
    assert abs(e["sparse"]["block_kept_share"] - e["kept"]) < 1e-6
    assert abs(e["sparse"]["block_forced_share"] - e["forced"]) < 1e-6
    assert e["sparse"]["block_tile_share"] == 1.0
    assert not any(refuses(e) for refuses in REFUSED_BY.values())


def test_a_control_reaches_the_program_that_is_timed(cell):
    """The timed step is built from ``model_config(model, sizes, control)``:
    the controls of the program change that config, and nothing else of it."""
    model, sizes = cell.config["model"], cell.config["train"]
    true = train_sala.model_config(model, sizes)
    changed = {}
    for control in train_sala.CONTROLS:
        cfg = train_sala.model_config(model, sizes, control)
        changed[control] = {f.name for f in dataclasses.fields(cfg)
                            if getattr(cfg, f.name) != getattr(true, f.name)}
    assert changed == {
        "fp8_weights": set(), "no_layer_factor": {"lightning"},
        "no_local_blocks": {"block_sparse"}, "scale_depth_1": {"residual_scale"},
        "bf16_state": set(), "reference_default_precision": set(), "half_batch": set(),
        "unchanged_state": set()}
    build = lambda control: train_sala.model_config(model, sizes, control)  # noqa: E731
    assert build("scale_depth_1").residual_scale == pytest.approx(1 / math.sqrt(32), rel=1e-12)
    assert build("no_layer_factor").lightning == dataclasses.replace(true.lightning, depth=10**9)
    assert build("no_local_blocks").block_sparse == dataclasses.replace(
        true.block_sparse, window_size=1)


def test_an_unknown_control_is_refused_before_a_cluster_starts(monkeypatch, cell):
    from benchmark.runners import Context

    monkeypatch.setenv("BENCH_SALA_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_SALA_CONTROL"):
        train_sala.run(ctx)


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_kernels(cell):
    tail = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    scope = lambda path: f', frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}'  # noqa: E731
    fast = ["lightning_fwd", "lightning_bwd"]
    blk = ["attn_blk_fwd", "attn_blk_bwd_dq", "attn_blk_bwd_dkdv"]
    ops = {f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
           + scope("stack/attn/lightning_scan"): [1.0, 2] for i, n in enumerate(fast)}
    ops.update({f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail
                + scope("stack/attn/sparse_attn"): [1.0, 2] for i, n in enumerate(blk)})
    ops["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/attn/sparse_select")] = [4.0, 4]
    ops["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
        + scope("stack/attn/lightning_out")] = [1.0, 1]
    manifest = Manifest()
    readers = {m: json.load(open(manifest.reader_file(m))) for m in NEW_METRICS}
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, reader in readers.items():
        assert declared[name]["workloads"] == [CELL]
        for k in ("layer", "unit", "moves"):
            assert reader[k] == declared[name][k]
        assert name in cell.declared(True) and name not in cell.declared(False)
    assert {declared[m]["layer"] for m in NEW_METRICS} == {
        "models/lightning", "models/block_sparse", "ops/ kernels"}

    def read(ops, seconds):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "attn": {"block_kept_share": 0.4346},
               "lightning": {"least_seconds": seconds[0], "seconds": seconds[1]},
               "blk": {"least_seconds": seconds[2], "seconds": seconds[3]}}
        return layer_metrics.read_all(readers, obs)

    assert read(ops, (0.5, 2.0, 1.5, 3.0)) == {
        "kernel.lightning_share.train": 10.0, "kernel.attn_blk_share.train": 15.0,
        "scope.lightning_scan_share.train": 10.0, "scope.sparse_select_share.train": 20.0,
        "scope.sparse_attn_share.train": 15.0, "kernel.lightning_roofline.train": 25.0,
        "kernel.attn_blk_roofline.train": 50.0, "attn.block_kept_share": 0.4346}
    # no such scope and no such call (a CPU rehearsal; an older program): the
    # runner hands the window's seconds for the calls' own, and each reads 0
    bare = {"%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
            + scope("stack/attn"): [1.0, 1]}
    none = read(bare, (0.0, 25.0, 0.0, 25.0))
    assert [none[m] for m in NEW_METRICS[:7]] == [0.0] * 7
    for kernel in fast + blk:
        own = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
        assert trace_reduce.matching(ops, own) == (1.0, 2)
    # an op that only MENTIONS a scope (an operand's name) is not in it
    mention = {
        "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %sparse_select.1), kind=kLoop": [1.0, 1]}
    assert trace_reduce.matching(
        mention, readers["scope.sparse_select_share.train"]["params"]["pattern"]) == (0.0, 0)


def test_the_cell_joins_the_accepted_metrics_that_are_true_of_it(cell):
    """The step's own metrics and the top-level scopes; no other
    configuration's kernels or scopes, and not the key-set kernels' metrics
    (``attn_sel_*`` match no ``attn_blk_*`` call). The plain flash kernels'
    three shares list it and read 0 here (no layer of it calls them):
    ``test_bm_kernel_names.py`` holds every cell but the sparse one to them."""
    manifest = Manifest()
    joined = {m["name"] for m in manifest.doc["per_layer"] if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "train.mfu", "train.step_ms", "train.data_wait_ms", "train.report_ms",
        "device.idle_share.train", "kernel.custom_call_share.train",
        *(f"kernel.flash_{part}_share.train" for part in ("fwd", "bwd_dq", "bwd_dkdv")),
        *(f"scope.{s}_share.train" for s in (
            "attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped"))}
    assert set(cell.declared(False)) == {"train_tok_s_chip", "setup_s"}
    with open(manifest.reader_file("kernel.attn_sel_share.train")) as f:
        sel = json.load(f)["params"]["pattern"]
    blk = '%attn_blk_fwd.1 = bf16[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    assert trace_reduce.matching({blk: [1.0, 1]}, sel) == (0.0, 0)
    assert manifest.problems() == []
