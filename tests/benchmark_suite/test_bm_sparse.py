"""The latent-attention configuration with a learned selection of keys
(dots3-note-prev: its first five layers, a chip's share of the experts and of
the vocabulary), its counts, and the runner's limits against the controls
they are meant to refuse, at the rehearsal size on the CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops_sparse, layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from benchmark.runners import RunFailure, train_sparse

CELL = "dots3-note-prev.train-8k-sparse"
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["kernel.attn_sel_share.train", "kernel.attn_sel_roofline.train",
               "kernel.attn_win_share.train", "kernel.attn_win_roofline.train",
               "kernel.dsa_index_share.train", "kernel.dsa_index_roofline.train",
               "attn.selected_share"]


def catalog() -> dict:
    """The row of the model-configs guide's catalog, where this sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "dots3-note-prev")


@pytest.fixture(scope="module")
def cell():
    return Manifest().cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model in float32 with seeded weights, the norms and the
    selection biases moved as the runner moves them, and one layer-check input."""
    from ray_tpu.models import init_params

    with open(os.path.join(HERE, "rehearse-sparse.json")) as f:
        doc = json.load(f)
    cfg = train_sparse.model_config(doc["model"], doc["train"], remat_policy="attn",
                                    dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = train_sparse.seed_biases(train_sparse.seed_norms(init_params(cfg, key), key), key)
    h = jax.random.normal(jax.random.PRNGKey(5), (128, cfg.hidden), cfg.dtype)
    pick = lambda slot: jax.tree.map(lambda a: a[0], params["layers"][slot])  # noqa: E731
    return doc, cfg, params, h, (pick("slot0"), pick("slot1"))


def test_the_configuration_keeps_every_published_number_but_the_four_cut(cell):
    model, doc, row = cell.config["model"], Manifest().doc, catalog()
    assert cell.config["reduced"] == REDUCED and cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "layer_types":
            assert model[key] == value[:5], key
        elif key in REDUCED:
            assert model[key] < value, key
        else:
            assert model[key] == value and type(model[key]) is type(value), key
    assert (model["num_hidden_layers"], model["n_routed_experts"], model["vocab_size"]) \
        == (5, 8, 19008)
    # the leading dense layer and a whole period after it, at least 8 experts,
    # an eighth of the vocabulary: the guide's floors
    assert model["layer_types"] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert model["first_k_dense_replace"] == 1 and model["vocab_size"] * 8 == 152064
    assert set(model) - set(row["config"]) == {"n_routed_experts_published", "router_width",
                                               "experts_held"}
    assert model["router_width"] == model["n_routed_experts_published"] == 256
    assert model["experts_held"] == [0, 7]
    # the same keys stand at the top level of the file, where the contract
    # compares a catalogued model's numbers
    assert {k: cell.config[k] for k in model} == model
    entry = next(c for c in doc["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cell.config["source"] and entry["reduced"] == REDUCED
    for key in ("source", "assumed", "deployment", "parameters", "reduced_why", "memory"):
        assert cell.config[key], key
    for point in ("lora_rescale", "gate", "indexer_training", "router_bias", "rope_pairs",
                  "ties", "left_out", "index_precision"):
        assert cell.config["assumed"][point], point
    assert "32 chips share each layer" in cell.config["deployment"]
    assert cell.chips == 1 and cell.traffic["runner"] == "train_sparse"
    assert cell.traffic["seq"] == 8192 and cell.config["train"]["batch"] == 2
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_program_is_told_the_published_widths_and_the_share(cell):
    cfg = train_sparse.model_config(cell.config["model"], cell.config["train"])
    assert cfg.lead_pattern == ("mla",) and cfg.n_periods == 1
    assert cfg.layer_pattern == ("mla", "mla_win", "mla_win", "mla_win")
    full, window = cfg.mla, cfg.mla_window
    assert dataclasses.astuple(full) == (128, 1024, 512, 128, 64, 128, 8e7, 0, 64, 128, 2048,
                                         True, True)
    assert dataclasses.astuple(window) == (64, 1024, 1024, 192, 64, 128, 5e4, 513, 0, 0, 0,
                                           True, True)
    assert (cfg.hidden, cfg.lead_intermediate, cfg.intermediate, cfg.moe_shared) \
        == (5120, 13824, 1536, 1536)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_bias_rate) \
        == (256, 8, (0, 8), "sigmoid", 0.001)
    assert cfg.moe_norm_topk and not cfg.moe_shared_gate and not cfg.norm_plus_one
    assert (cfg.vocab_size, cfg.moe_aux_weight, cfg.norm_eps) == (19008, 0.0001, 1e-5)


def test_parameter_counts_by_hand(cell):
    model = cell.config["model"]
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 128 * 128 * 5120
            + 5120 * 128)
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    window = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320 + 64 * 128 * 5120
              + 5120 * 64)
    expert = 3 * 5120 * 1536
    assert (full + index, window, expert, 3 * 5120 * 13824) \
        == (144_048_128, 90_832_896, 23_592_960, 212_336_640)
    norms = lambda a, indexed: 1024 + a + (256 if indexed else 0) + 2 * 5120  # noqa: E731
    experts = 256 * 5120 + 256 + 9 * expert            # router, its bias, shared + 8 held
    total = (full + index + norms(512, True) + 212_336_640
             + full + index + norms(512, True) + experts
             + 3 * (window + norms(1024, False) + experts)
             + 2 * 5120 * 19008 + 5120)
    assert flops_sparse.param_count(model) == total == cell.config["parameters"] == 1_822_230_016


def test_flops_by_hand_and_equal_to_the_programs_count(cell):
    from ray_tpu.models.llama import train_flops_per_token

    model, seq = cell.config["model"], cell.traffic["seq"]
    kept_full = (2048 * 2049 / 2 + 6144 * 2048) / 8192           # keys a query keeps
    kept_window = (513 * 514 / 2 + (8192 - 513) * 513) / 8192
    assert kept_full / ((seq + 1) / 2) == pytest.approx(0.4375, abs=6e-5)
    full = (2 * (144_048_128 - 9_371_648 + 9_371_648 * 2 / 3)
            + 2 * 128 * 320 * kept_full + 2 * 64 * 128 * (seq + 1) / 2)
    window = 2 * 90_832_896 + 2 * 64 * 384 * kept_window
    experts = 2 * (5120 * 256 + 3 * 5120 * 1536 + 8 * (8 / 256) * 3 * 5120 * 1536)
    parts = flops_sparse.forward_flops_by_part(model, seq)
    want = {"full_attention": 2 * full, "window_attention": 3 * window,
            "dense_mlp": 2 * 212_336_640, "experts": 4 * experts, "head": 2 * 5120 * 19008}
    assert parts == pytest.approx(want, rel=1e-12)
    by_hand = 3 * sum(want.values())
    assert flops_sparse.train_flops_per_token(model, seq) == pytest.approx(by_hand, rel=1e-12)
    assert by_hand == pytest.approx(7.4267e9, rel=1e-4)
    cfg = train_sparse.model_config(model, cell.config["train"])
    assert train_flops_per_token(cfg, seq) == pytest.approx(by_hand, rel=1e-12)


def test_the_counts_are_the_programs_own(tiny):
    """``param_count`` against the leaves ``init_params`` makes, and every new
    kernel's operations and bytes against what it records of itself."""
    from ray_tpu.ops import trace_log

    doc, cfg, params, h, layers = tiny
    model = doc["model"]
    assert flops_sparse.param_count(model) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    seq = h.shape[0]

    def loss(layer, spec):
        from ray_tpu.models.mla import mla_mixer

        y, aux = mla_mixer(h[None], layer, spec, config=cfg, positions=jnp.arange(seq))
        return y.sum() + aux.get("index_loss", 0.0)

    jax.grad(loss)(layers[0], cfg.mla)
    jax.grad(loss)(layers[1], cfg.mla_window)
    recorded = trace_log.kernel_costs()
    want = {**flops_sparse.attention_kernel_costs(model, "mla", 1, seq),
            **flops_sparse.attention_kernel_costs(model, "mla_win", 1, seq),
            **flops_sparse.index_kernel_costs(model, 1, seq)}
    assert len(want) == 10
    for kernel, (kernel_flops, kernel_bytes) in want.items():
        got = recorded[kernel]
        # the rehearsal computes in float32: 4-byte operands where the count has bf16's 2
        assert got["flops"] == pytest.approx(kernel_flops, rel=1e-12), kernel
        assert kernel_bytes <= got["bytes"] <= 2 * kernel_bytes, kernel
    # at the cell's size: the kept keys' work, under the walked triangle's
    big = Manifest().cell(CELL).config["model"]
    sel = flops_sparse.attention_kernel_costs(big, "mla", 1, 8192)
    assert sel["attn_sel_fwd"][0] == 2 * 128 * (2048 * 2049 / 2 + 6144 * 2048) * 320
    assert sel["attn_sel_bwd_dkdv"][0] == 2 * sel["attn_sel_fwd"][0]
    assert sel["attn_sel_fwd"][0] / (2 * 128 * 8192 * 8193 / 2 * 320) == pytest.approx(
        0.4375, abs=1e-4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops_sparse.roofline_seconds(*sel["attn_sel_fwd"], peaks) \
        == sel["attn_sel_fwd"][0] / 197e12


def test_the_runner_refuses_a_model_type_it_cannot_build(cell):
    with pytest.raises(RunFailure, match="builds no model of type"):
        train_sparse.model_config({**cell.config["model"], "model_type": "qwen3_next"},
                                  cell.config["train"])


def test_seeded_biases_move_every_bias_and_nothing_else(tiny):
    from ray_tpu.models import init_params

    _, cfg, _, _, _ = tiny
    key = jax.random.PRNGKey(3)
    plain = init_params(cfg, key)
    moved = train_sparse.seed_biases(plain, key)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(plain)[0],
                            jax.tree.leaves(moved)):
        is_bias = str(path[-1].key) == "router_bias"
        assert bool((a != b).any()) == is_bias, jax.tree_util.keystr(path)
        if is_bias:
            assert float(jnp.abs(b).max()) <= train_sparse.BIAS_SPREAD


# the check of ``layer_errors``'s readings that refuses each control, by the
# limits' own names; ``share`` is the count of keys attended
REFUSED_BY = {
    "window_512": lambda e: e["window"]["max"] > train_sparse.MIXER_RTOL,
    "no_selection": lambda e: e["full"]["selection_agreement"]
    < train_sparse.SELECTION_AGREEMENT,
    "top_2047": lambda e: abs(e["full"]["selected_share"] - e["full"]["ref_selected_share"])
    > train_sparse.SELECTED_SHARE_ATOL,
    "no_rescale": lambda e: min(e["full"]["max"], e["window"]["max"]) > train_sparse.MIXER_RTOL,
    "no_gate": lambda e: min(e["full"]["max"], e["window"]["max"]) > train_sparse.MIXER_RTOL,
    "softmax_router": lambda e: e["experts"]["max"] > train_sparse.LAYER_RTOL,
    "bias_ignored": lambda e: e["experts"]["max"] > train_sparse.LAYER_RTOL,
    "fp8_weights": lambda e: min(e["full"]["max"], e["window"]["max"], e["experts"]["max"])
    > train_sparse.MIXER_RTOL,
}


def _layer_readings(tiny, control):
    doc, _, _, h, ref_layers = tiny
    cfg = train_sparse.model_config(doc["model"], doc["train"], control, remat_policy="attn",
                                    dtype=jnp.float32)
    from ray_tpu.models import param_axes

    axes = param_axes(cfg)["layers"]
    layers = tuple(train_sparse.as_program(layer, {k: None for k in axes[slot]})
                   for layer, slot in zip(ref_layers, ("slot0", "slot1")))
    if control == "fp8_weights":
        layers = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), layers)
    out = train_sparse.layer_errors(cfg, train_sparse.reference_arch(doc["model"]), layers,
                                    ref_layers, h)
    out["share"] = train_sparse.expected_selected_share(h.shape[0], doc["model"]["index_topk"])
    return out


def test_the_unfaulted_layers_read_far_under_every_limit(tiny):
    e = _layer_readings(tiny, None)
    assert max(e["full"]["max"], e["window"]["max"], e["experts"]["max"]) < 1e-4
    assert e["full"]["selection_agreement"] == 1.0
    assert abs(e["full"]["selected_share"] - e["full"]["ref_selected_share"]) < 1e-6
    assert e["full"]["selected_share"] >= e["share"] - 1e-6
    assert abs(e["full"]["index_loss"] - e["full"]["ref_index_loss"]) \
        < 1e-4 * e["full"]["ref_index_loss"]
    assert not any(refuses(e) for refuses in REFUSED_BY.values())
    assert e["experts"]["dropped"] == 0 and e["experts"]["rows"] == 128 * 3


# the controls that change what the timed STEP does and no layer: refused by
# the step's own comparison (below, and through the harness)
STEP_CONTROLS = {"half_batch", "unchanged_state"}


@pytest.mark.parametrize("control", list(REFUSED_BY))
def test_each_control_is_refused_by_its_limit(tiny, control):
    assert set(REFUSED_BY) | STEP_CONTROLS == set(train_sparse.CONTROLS)
    assert REFUSED_BY[control](_layer_readings(tiny, control)), control


def _bf16_step(after_is_start: bool):
    """A matrix drawn around 0 and a norm's weights of 0.5-1.5, both bf16,
    one adafactor step from a seeded gradient on both sides alike; the
    program's side rounded as the leaf rounds, or left where it was."""
    import optax

    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    start = {"w": (0.02 * jax.random.normal(keys[0], (64, 128))).astype(jnp.bfloat16),
             "attn_norm": jax.random.uniform(keys[1], (2048,), minval=0.5,
                                             maxval=1.5).astype(jnp.bfloat16)}
    grads = {"w": jax.random.normal(keys[2], (64, 128)),
             "attn_norm": jax.random.normal(keys[3], (2048,))}
    opt = optax.adafactor(0.001)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), start)
    updates, state = opt.update(grads, opt.init(p32), p32)
    after = jax.tree.map(lambda p, u: (p + u).astype(jnp.bfloat16), p32, updates)
    if after_is_start:
        after, state = start, opt.init(p32)
    names = {jax.tree_util.keystr(p): g for p, g in
             jax.tree_util.tree_flatten_with_path(grads)[0]}
    return train_sparse.step_errors(opt, start, after, state, start, names)


def test_a_leaf_whose_bf16_elements_a_step_cannot_move_is_read_and_not_judged():
    step = _bf16_step(after_is_start=False)
    moved = step["by_leaf"]["ref_moved_share"]
    assert moved["['w']"] > 5 * train_sparse.UPDATE_MIN_MOVED
    assert moved["['attn_norm']"] < train_sparse.UPDATE_MIN_MOVED / 5
    assert step["update"]["leaf"] == "['w']" and step["update"]["worst"] < 1e-6
    assert set(step["by_leaf"]["update"]) == {"['w']", "['attn_norm']"}
    assert step["grad_stats"]["worst"] < 1e-6


def test_a_state_left_unchanged_reads_one_and_is_refused():
    step = _bf16_step(after_is_start=True)
    assert step["update"]["worst"] == pytest.approx(1.0)
    assert step["grad_stats"]["worst"] == pytest.approx(1.0)
    assert 1.0 > train_sparse.UPDATE_RTOL and 1.0 > train_sparse.GRAD_STATS_RTOL


def test_the_reference_step_block_by_block_is_the_gradient_of_the_references_loss(tiny):
    from benchmark.reference import latent_sparse_decoder as ref

    doc, cfg, params, _, _ = tiny
    arch, sizes = train_sparse.reference_arch(doc["model"]), doc["train"]
    rows = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, cfg.vocab_size)
    (want, seen), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, rows, arch, aux_weight=sizes["aux_loss_weight"], return_seen=True),
        has_aux=True))(params)
    key_sets = jnp.stack([ref.logits(params, row, arch)[1]["selection"] for row in rows])
    got, got_seen, got_g = train_sparse.reference_step(params, rows, key_sets, arch, sizes)
    assert got == pytest.approx(float(want), rel=1e-6)
    for k in ("ce", "balance", "index_loss", "own_selected_share"):
        assert got_seen[k] == pytest.approx(float(seen[k]), rel=1e-5), k
    assert (got_seen["rows_per_expert"] == seen["rows_per_expert"]).all()
    assert jnp.allclose(got_seen["logits"], seen["logits"], atol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    assert set(got_g) == {jax.tree_util.keystr(p) for p, _ in flat}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        assert got_g[name].shape == leaf.shape and got_g[name].dtype == leaf.dtype, name
        scale = float(jnp.abs(leaf).max())
        assert float(jnp.abs(got_g[name] - leaf).max()) <= 2e-5 * max(scale, 1e-3), name


def test_the_bias_check_reads_the_rule_and_a_missing_leaf():
    import numpy as np

    rate = 0.001
    start = {"layers": {f"slot{i}": {"router_bias": jnp.full((1, 8), 0.01 * i)}
                        for i in range(2)}}
    rows = np.array([[5, 1, 9, 4, 4, 3, 6, 0], [4, 4, 4, 4, 4, 4, 4, 4]])
    stepped = {"layers": {
        f"slot{i}": {"router_bias": start["layers"][f"slot{i}"]["router_bias"]
                     + rate * np.sign(rows[i].mean() - rows[i])} for i in range(2)}}
    assert train_sparse.bias_errors(start, stepped, rows, rate)["agreement"] == 1.0
    # a step of another size, or none where the rule steps, agrees nowhere
    assert train_sparse.bias_errors(start, start, rows, rate)["agreement"] < 0.5
    assert train_sparse.bias_errors(start, {"layers": {}}, rows, rate)["agreement"] == 0.0


def test_the_new_readers_parse_and_read_0_on_a_trace_without_their_kernels():
    tail = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    names = ["attn_sel_fwd", "attn_sel_bwd_dq", "attn_sel_bwd_dkdv", "attn_win_fwd",
             "attn_win_bwd_dq", "attn_win_bwd_dkdv", "dsa_index_fwd", "dsa_index_bwd_dq",
             "dsa_index_bwd_dk", "dsa_probs"]
    with_kernels = {f"%{n}.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p)" + tail: [1.0, 2]
                    for i, n in enumerate(names)}
    without = {"%flash_fwd.1 = bf16[8]{0} custom-call(bf16[8]{0} %p)" + tail: [1.0, 1],
               "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %attn_sel_fwd.3), kind=kLoop": [1.0, 1],
               "%moe_gmm.1 = bf16[8]{0} custom-call(bf16[8]{0} %p)" + tail: [1.0, 1]}
    manifest = Manifest()
    readers = {m: json.load(open(manifest.reader_file(m))) for m in NEW_METRICS}
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, reader in readers.items():
        assert declared[name]["workloads"] == [CELL]
        for k in ("layer", "unit", "moves"):
            assert reader[k] == declared[name][k]

    def read(ops, family_seconds):
        obs = {"trace": {"ops": ops, "busy_s_per_device": [20.0], "window_s": 25.0},
               "attn": {"selected_share": 0.4375},
               **{f: {"least_seconds": least, "seconds": took}
                  for f, (least, took) in family_seconds.items()}}
        return layer_metrics.read_all(readers, obs)

    got = read({**with_kernels, **without},
               {"sel": (1.5, 6.0), "win": (1.0, 6.0), "dsa": (4.0, 8.0)})
    assert got == {"kernel.attn_sel_share.train": 15.0, "kernel.attn_sel_roofline.train": 25.0,
                   "kernel.attn_win_share.train": 15.0,
                   "kernel.attn_win_roofline.train": pytest.approx(100 / 6),
                   "kernel.dsa_index_share.train": 20.0, "kernel.dsa_index_roofline.train": 50.0,
                   "attn.selected_share": 0.4375}
    # no such call (a CPU rehearsal; an older program): the runner hands the
    # window's seconds for the calls' own, and every share reads 0
    none = read(without, {f: (0.0, 25.0) for f in ("sel", "win", "dsa")})
    assert [none[m] for m in NEW_METRICS[:6]] == [0.0] * 6
    for kernel in names:
        own = rf'^%{kernel}(\.[\w.\-]+)? = .*custom_call_target="tpu_custom_call"'
        assert trace_reduce.matching(with_kernels, own) == (1.0, 2)


# what a rehearsal through the harness must end as: the checks that refuse a
# control ("" = none may)
# (the uncontrolled rehearsal, both trace modes, is test_bm_rehearsal.py's)
REHEARSALS = {"top_2047": "selected_share_is_the_count",
              "bias_ignored": "bias_steps_as_the_reference",
              "half_batch": "update_matches_reference"}


@pytest.mark.parametrize("control", list(REHEARSALS), ids=lambda c: c or "uncontrolled")
def test_a_rehearsal_ends_correct_and_a_control_not(control):
    import subprocess
    import sys

    from benchmark.manifest import ROOT

    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    if control:
        env["BENCH_SPARSE_CONTROL"] = control
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed", str(2**31 + 43),
         "--seconds", "2", "--trace", "0" if control else "1", "--rehearse"], cwd=ROOT,
        text=True, timeout=420, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert out.returncode == 0, out.stdout[-3000:]
    lines = out.stdout.rstrip("\n").split("\n")
    said = next(json.loads(x) for x in lines if x.startswith("{") and '"checks"' in x)
    refused = {name for name, ok in said["checks"].items() if not ok}
    last = json.loads(lines[-1])
    assert last["failed"] == 0
    if control:
        assert said["control"].startswith(control) and REHEARSALS[control] in refused, refused
        assert last["correct"] is False
    else:
        assert not refused and last["correct"] is True and said["rows_wrong"] == []
        assert set(NEW_METRICS) <= set(last["metrics"])
        assert last["metrics"]["attn.selected_share"]["value"] == pytest.approx(
            train_sparse.expected_selected_share(64, 16), abs=1e-4)
        assert [last["metrics"][m]["value"] for m in NEW_METRICS[:6]] == [0.0] * 6


def test_an_unknown_control_is_refused_before_a_cluster_starts(monkeypatch, cell):
    from benchmark.runners import Context

    monkeypatch.setenv("BENCH_SPARSE_CONTROL", "fp4_weights")
    ctx = Context(cell=cell, seed=1, seconds=1.0, trace=False, rehearse=None,
                  t_start_wall=0.0, t_start_mono=0.0, say=lambda _: None)
    with pytest.raises(RunFailure, match="BENCH_SPARSE_CONTROL"):
        train_sparse.run(ctx)
    monkeypatch.delenv("BENCH_SPARSE_CONTROL")
    monkeypatch.setenv("BENCH_SPARSE_WITNESS", "no_gate")
    with pytest.raises(RunFailure, match="BENCH_SPARSE_WITNESS"):
        train_sparse.run(ctx)
