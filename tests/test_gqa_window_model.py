"""The decoder of full and window layers of grouped-query attention by spec
(``models/gqa.py``: two head counts, YaRN, a window, a head-wise gate), a
leading dense layer and softmax-routed experts under a routed scale
(``models/moe.py``) against its plain reference
(``benchmark/reference/windowed_moe_decoder.py``) on seeded weights, in
float32 on the CPU with the Pallas kernels interpreted, at a size where the
window (5) drops keys and YaRN's factor moves frequencies."""

import dataclasses
import zlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import jaxpr_walk

from benchmark.reference import windowed_moe_decoder as ref
from ray_tpu.models import PRESETS, init_params, loss_fn
from ray_tpu.models.gqa import gqa_mixer
from ray_tpu.models.moe import moe_block
from ray_tpu.ops import attention, trace_log
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.rope import yarn_frequencies

CFG = dataclasses.replace(PRESETS["window-moe-debug"], dtype=jnp.float32,
                          remat_policy="attn")
SEQ = 32


def arch_of(c) -> dict:
    return {"kinds": {"gqa": dataclasses.asdict(c.gqa),
                      "gqa_win": dataclasses.asdict(c.gqa_window)},
            "pattern": c.layer_pattern, "lead_pattern": c.lead_pattern,
            "norm_eps": c.norm_eps, "top_k": c.moe_top_k, "norm_topk": c.moe_norm_topk,
            "held_first": c.moe_held[0] if c.moe_held else 0,
            "routed_scale": c.moe_routed_scale}


ARCH = arch_of(CFG)


@pytest.fixture(scope="module")
def params():
    p = jax.jit(lambda key: init_params(CFG, key))(jax.random.PRNGKey(0))

    def move(path, leaf):  # norms off 1: one left out must show
        if not str(getattr(path[-1], "key", "")).endswith("norm"):
            return leaf
        # crc32 and not ``hash``, which differs from one process to the next
        key = jax.random.fold_in(jax.random.PRNGKey(1),
                                 zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        return leaf + jax.random.uniform(key, leaf.shape, minval=-0.5, maxval=0.5)

    return jax.tree_util.tree_map_with_path(move, p)


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(5), (SEQ, CFG.hidden))


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def layer_of(params, slot):
    return jax.tree.map(lambda a: a[0], params["layers"][slot])


def test_yarn_frequencies_at_the_published_numbers_match_values_worked_by_hand():
    # Laguna-S-2.1's full layers: 64 rotated features, theta 500,000, factor
    # 128, trained at 8,192, beta_fast 32, beta_slow 1
    d, theta, factor, length = 64, 500_000.0, 128.0, 8192
    dim = lambda r: d * math.log(length / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (9, 18)
    assert abs(dim(32) - 9.04) < 0.01 and abs(dim(1) - 17.49) < 0.01
    got = np.asarray(yarn_frequencies(d, theta=theta, factor=factor, original_length=length,
                                      beta_fast=32.0, beta_slow=1.0))
    assert got.shape == (32,) and got.dtype == np.float32
    f = lambda i: theta ** (-2 * i / d)  # noqa: E731
    # pairs 0-9 turn often enough to keep their frequency, 18-31 are slowed
    # 128-fold, those between are blended: pair 13 keeps 5/9 of its own
    want = {0: 1.0, 9: f(9), 13: f(13) * (5 / 9 + 4 / 9 / 128), 18: f(18) / 128,
            31: theta ** (-62 / 64) / 128}
    assert abs(want[31] - 2.354e-8) < 1e-11
    for i, w in want.items():
        assert abs(got[i] - w) <= 2e-6 * w, (i, got[i], w)
    assert (np.diff(got) < 0).all()
    # the reference writes the same equations out for itself
    assert np.allclose(got, np.asarray(ref.yarn_inv_freq(d, theta, factor, length, 32.0, 1.0)),
                       rtol=2e-6, atol=0)
    # the preset's YaRN moves frequencies too: pair 0 kept, 2-3 slowed 8-fold
    y = CFG.gqa.yarn
    small = yarn_frequencies(8, theta=100.0, factor=y.factor, original_length=y.original_length,
                             beta_fast=y.beta_fast, beta_slow=y.beta_slow)
    plain = 100.0 ** (-np.arange(0, 8, 2) / 8)
    assert np.allclose(small / plain, [1, (1 + 1 / 8) / 2, 1 / 8, 1 / 8], rtol=1e-6)


GROUPED = {
    # name: (query heads, kv heads, window, sequence) at 32-row blocks
    "win_9to1_h18": (18, 2, 40, 128),
    "win_9to1_h9": (9, 1, 5, 128),
    "full_6to1_h12": (12, 2, None, 128),
    "full_6to1_h6": (6, 1, None, 128),
    # Laguna's and SmallThinker's ratios, folded: a window narrower than the
    # query block, equal to it and four times it (the band of the first query
    # blocks is cut by position 0 in each)
    "fold_72to8_narrow": (72, 8, 20, 128),
    "fold_72to8_equal": (72, 8, 32, 128),
    "fold_72to8_four_blocks": (72, 8, 128, 256),
    "fold_28to4_narrow": (28, 4, 20, 128),
    "fold_28to4_equal": (28, 4, 32, 128),
    "fold_28to4_four_blocks": (28, 4, 128, 256),
    "win_one_head_a_group": (8, 8, 40, 128),
}
# the same folded calls with no room for a query block's band in one tile: the
# band is walked in key blocks under the online softmax (the 4,096-key band's way)
WALKED = ("fold_72to8_narrow", "fold_28to4_equal", "fold_28to4_four_blocks")


@pytest.mark.parametrize("case", list(GROUPED) + [f"{name}_walked" for name in WALKED])
def test_attention_kernels_at_grouped_ratios_match_mha_reference(case, monkeypatch):
    """``attn_win_*`` at 9 and 7 query heads a kv head and ``flash_*`` at 6,
    values and all three gradients. Under a window and grouped queries a tile's
    rows are a kv head's whole group (``heads_a_tile``), dK and dV leave the
    kernel at the KV heads' count and nothing sums them afterwards; one head a
    group under a window takes the unfolded kernels."""
    hq, hkv, window, s = GROUPED[case.removesuffix("_walked")]
    if case.endswith("_walked"):
        monkeypatch.setattr(attention, "_BAND_TILE_BYTES", 0)
    key = jax.random.PRNGKey(3)
    b, d = 1, 32
    q = jax.random.normal(key, (b, hq, s, d))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, hkv, s, d)) for i in (1, 2))
    got = jax.jit(lambda *x: flash_attention(*x, block_q=32, block_k=32, window=window))
    want = jax.jit(lambda *x: mha_reference(*x, window=window))
    assert rel(got(q, k, v), want(q, k, v)) < 1e-5
    grad = jax.grad(lambda *x: jnp.sum(got(*x) ** 2), (0, 1, 2))
    g = jax.jit(grad)(q, k, v)
    w = jax.jit(jax.grad(lambda *x: jnp.sum(want(*x) ** 2), (0, 1, 2)))(q, k, v)
    assert max(rel(a, b_) for a, b_ in zip(g, w)) < 1e-5
    if window is None:
        return
    costs = trace_log.kernel_costs()
    for part in ("fwd", "bwd_dq", "bwd_dkdv"):
        assert costs[f"attn_win_{part}"]["heads_a_tile"] == hq // hkv
    one_tile = hq > hkv and not case.endswith("_walked")
    assert (costs["attn_win_fwd"]["grid_steps"] == s // 32) == one_tile
    eqns = list(jaxpr_walk.equations(jax.make_jaxpr(grad)(q, k, v).jaxpr))
    dkdv = next(e for e in eqns if e.primitive.name == "pallas_call"
                and str(e.params["name"]) == "attn_win_bwd_dkdv")
    assert [o.aval.shape for o in dkdv.outvars] == [(b, hkv, s, d)] * 2
    assert "reduce_sum" not in {e.primitive.name for e in eqns[eqns.index(dkdv) + 1:]}


FAULTS = {
    None: {},
    "no_window": {"window": 0},
    "plain_rope": {"yarn": None},
    "rope_on_the_whole_head": {"rotary_dim": 0},
    "no_gate": {"gate": "none"},
    "four_of_six_heads": {"heads": 4},
}


@pytest.mark.parametrize("kind,fault", [("gqa", None), ("gqa_win", None), ("gqa_win", "no_window"),
                                        ("gqa", "plain_rope"), ("gqa", "rope_on_the_whole_head"),
                                        ("gqa", "no_gate"), ("gqa_win", "four_of_six_heads")])
def test_each_mixer_kind_matches_the_reference_and_a_planted_fault_does_not(
        params, hidden, kind, fault):
    spec = CFG.gqa if kind == "gqa" else CFG.gqa_window
    layer = layer_of(params, "slot3" if kind == "gqa" else "slot0")
    want = jax.jit(lambda h, w: ref.gqa_mixer(h, w, ARCH["kinds"][kind]))(hidden, layer)
    given = layer
    if fault == "four_of_six_heads":  # the first two heads of each kv head's three
        take = np.array([0, 1, 3, 4])
        given = {**layer, "wq": layer["wq"][:, take], "wo": layer["wo"][take],
                 "w_attn_gate": layer["w_attn_gate"][:, take]}
    spec = dataclasses.replace(spec, **FAULTS[fault])
    got, aux = jax.jit(lambda h, w: gqa_mixer(
        h[None], w, spec, config=CFG, positions=jnp.arange(SEQ, dtype=jnp.int32)))(hidden, given)
    if fault is None:
        assert rel(got[0], want) < 2e-5
        if kind == "gqa_win":
            assert abs(float(aux["window_share"]) - ref.window_share(SEQ, 5)) < 1e-6
        else:
            assert aux == {}
    else:
        assert rel(got[0], want) > 0.05


@pytest.mark.parametrize("kind", ["gqa", "gqa_win"])
def test_a_mixer_under_a_mesh_runs_its_kernel_per_shard_and_gives_the_same(params, kind):
    """Rows over the data axes, kv-head groups over tp (2 kv heads, 4 or 6
    query heads: tp 2 divides each); a mesh that splits the sequence is
    refused, not run unsplit."""
    from ray_tpu.parallel import MeshConfig, create_mesh

    spec = CFG.gqa if kind == "gqa" else CFG.gqa_window
    layer = layer_of(params, "slot3" if kind == "gqa" else "slot0")
    h = jax.random.normal(jax.random.PRNGKey(7), (4, SEQ, CFG.hidden))
    run = lambda mesh: jax.jit(lambda h, w: gqa_mixer(  # noqa: E731
        h, w, spec, config=CFG, positions=jnp.arange(SEQ, dtype=jnp.int32), mesh=mesh))
    want, _ = run(None)(h, layer)
    mesh = create_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    text = run(mesh).lower(h, layer).as_text()
    assert "sdy.manual_computation" in text  # the shard_map
    got, aux = run(mesh)(h, layer)
    assert rel(got, want) < 1e-5
    assert set(aux) == ({"window_share"} if kind == "gqa_win" else set())
    with pytest.raises(NotImplementedError, match="sp"):
        run(create_mesh(MeshConfig(dp=4, sp=2))).lower(h, layer)


@pytest.mark.parametrize("fault", [None, "no_scale", "top_2", "not_renormalised",
                                   "softmax_over_the_held"])
def test_the_expert_layer_matches_the_reference_and_a_planted_fault_does_not(
        params, hidden, fault):
    layer = layer_of(params, "slot1")
    want, routing = jax.jit(lambda h, w: ref.expert_layer(
        h, w, top_k=3, norm_topk=True, first=0, scale=2.5))(hidden, layer)
    kw = dict(top_k=3, norm_topk=True, held=CFG.moe_held, routed_scale=2.5)
    given = layer
    if fault == "no_scale":
        kw["routed_scale"] = 1.0
    elif fault == "top_2":
        kw["top_k"] = 2
    elif fault == "not_renormalised":
        kw["norm_topk"] = False
    elif fault == "softmax_over_the_held":
        given, kw["held"] = {**layer, "router": layer["router"][:, :2]}, None
        kw["top_k"] = 2
    got, aux = jax.jit(lambda h, w: moe_block(h[None], w, **kw))(hidden, given)
    if fault is None:
        assert rel(got[0], want) < 2e-5
        assert int(aux["dropped"]) == 0 and int(aux["rows"].sum()) == SEQ * 3
        assert np.array_equal(np.asarray(aux["rows"]), np.asarray(routing["rows"]))
        assert abs(float(aux["held_share"]) - float(routing["rows"][:2].sum()) / (SEQ * 3)) < 1e-6
    else:
        assert rel(got[0], want) > 0.05


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(hidden):
    """Four chips hold two experts each of eight: what each share's held
    experts add, with the shared expert counted once, is the uncut
    reference's layer; the program's share equals the reference's."""
    whole_cfg = dataclasses.replace(CFG, moe_held=None)
    whole = jax.tree.map(lambda a: a[0], jax.jit(lambda key: init_params(whole_cfg, key)[
        "layers"]["slot0"])(jax.random.PRNGKey(7)))
    kw = dict(top_k=3, norm_topk=True, scale=2.5)
    layer_of_share = jax.jit(lambda h, w, first: ref.expert_layer(h, w, first=first, **kw)[0],
                             static_argnums=2)
    want = layer_of_share(hidden, whole, 0)
    shared = {k: v for k, v in whole.items() if k.startswith("w_shared")}
    routed = {k: v for k, v in whole.items() if not k.startswith("w_shared")}
    experts = ("w_gate", "w_up", "w_down")
    total = layer_of_share(hidden, {**shared, **{k: routed[k][:0] for k in experts},
                                    "router": routed["router"]}, 0)
    for first in range(0, 8, 2):
        share = {**routed, **{k: routed[k][first:first + 2] for k in experts}}
        part = layer_of_share(hidden, share, first)
        total = total + part
        if first == 4:  # one share through the program too: its range starts past 0
            got, _ = jax.jit(lambda h, w: moe_block(
                h[None], w, top_k=3, norm_topk=True, held=(first, 2), routed_scale=2.5))(
                hidden, share)
            assert rel(got[0], part) < 2e-5
    assert rel(total, want) < 1e-5


@pytest.fixture(scope="module")
def program_step():
    # every program under one ``jit``: op by op, the interpreted kernels take minutes
    return jax.jit(jax.value_and_grad(
        lambda p, rows: loss_fn(p, {"tokens": rows}, CFG, chunk_tokens=16, return_aux=True),
        has_aux=True))


@pytest.fixture(scope="module")
def reference_step(params, rows):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, rows, ARCH, aux_weight=CFG.moe_aux_weight, return_seen=True),
        has_aux=True))(params)


def test_loss_and_every_gradient_match_the_reference(params, rows, program_step, reference_step):
    """The whole stack: the loss, its terms and counters, and every leaf's
    gradient (the logits' own comparison runs in the benchmark's rehearsal,
    ``logits_match_reference``)."""
    (loss, aux), grads = program_step(params, rows)
    (want_loss, seen), want_grads = reference_step
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(aux["load_balance"]) - float(seen["balance"])) < 1e-5
    assert np.array_equal(np.asarray(aux["rows_per_expert"]), np.asarray(seen["rows_per_expert"]))
    assert abs(float(aux["attn_window_share"]) - ref.window_share(SEQ, 5)) < 1e-6
    assert int(aux["rows_dropped"]) == 0
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path, g in flat.items():
        assert rel(g, want_flat[path]) < 2e-4, jax.tree_util.keystr(path)
    # the same loss and gradients, a block at a time and by hand
    by_hand_loss, by_hand_seen, by_hand = ref.loss_and_grads(
        params, rows, ARCH, aux_weight=CFG.moe_aux_weight)
    assert abs(by_hand_loss - float(want_loss)) < 1e-5
    assert abs(by_hand_seen["balance"] - float(seen["balance"])) < 1e-6
    assert np.allclose(by_hand_seen["logits"], np.asarray(seen["logits"]), atol=1e-5)
    assert {jax.tree_util.keystr(p) for p in want_flat} == set(by_hand)
    for path, g in want_flat.items():
        assert rel(by_hand[jax.tree_util.keystr(path)], g) < 1e-5, jax.tree_util.keystr(path)


ADAFACTOR = optax.adafactor(1e-3)  # one object: ``step_errors`` compiles a leaf's readings once


@pytest.mark.parametrize("control", [None, "half_batch", "unchanged_state", "twice_as_far"])
def test_the_benchmarks_step_comparison_reads_0_on_the_programs_step_and_1_on_none(
        params, rows, program_step, reference_step, control):
    """``benchmark/runners/train_swa.step_errors`` on this model's step: the
    program's gradient through adafactor against the reference's through the
    same, a leaf at a time. The two controls of the runner that leave the
    program as it is (the others change a layer: the benchmark's suite), and
    a step twice as long."""
    from benchmark.runners import train_swa

    opt = ADAFACTOR
    given = rows
    if control == "half_batch":
        half = rows.reshape(-1)[:rows.size // 2]
        given = jnp.concatenate([half, half]).reshape(rows.shape)
    _, grads = program_step(params, given)
    if control == "twice_as_far":
        opt_far = optax.adafactor(2e-3)
        updates, state = jax.jit(opt_far.update)(grads, opt_far.init(params), params)
    else:
        updates, state = jax.jit(opt.update)(grads, opt.init(params), params)
    after = optax.apply_updates(params, updates)
    if control == "unchanged_state":
        after, state = params, opt.init(params)
    want_grads = {jax.tree_util.keystr(path): g for path, g in
                  jax.tree_util.tree_flatten_with_path(reference_step[1])[0]}
    e = train_swa.step_errors(opt, params, after, state, params, want_grads)
    assert e["leaves_judged"] >= 20
    readings = (e["update"]["worst"], e["update"]["median"], e["grad_stats"]["worst"])
    if control is None:
        # the readings the runner judges; ``update_rounded`` counts a
        # last-place flip on one side whole (0.03125 = one bf16 element of
        # one leaf), so the runner reports it unjudged and so does this
        assert max(readings) < 1e-3 and "worst" in e["update_rounded"], e
    elif control == "unchanged_state":
        assert readings == (1.0, 1.0, 1.0)
    elif control == "twice_as_far":
        assert abs(readings[1] - 1.0) < 1e-3 and readings[2] < 1e-3
    else:
        assert min(readings) > 3 * train_swa.UPDATE_ALONG_ATOL
        assert readings[2] > train_swa.GRAD_STATS_RTOL


def test_remat_attn_runs_each_attention_forward_once_and_the_kinds_count_their_own_flops():
    from ray_tpu.models.llama import MIXERS, train_flops_per_token

    c = CFG
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16)))(shapes))
    # the leading full layer, and a period of three window layers and a full one
    assert text.count("name=flash_fwd") == 2 and text.count("name=attn_win_fwd") == 3
    assert text.count("name=flash_bwd_dq") == 2 and text.count("name=attn_win_bwd_dkdv") == 3
    # the window kind counts its band's keys, the full kind the triangle's
    win, full = c.gqa_window, c.gqa
    band = 5 * (5 + 1) / 2 + (SEQ - 5) * 5
    assert MIXERS["gqa_win"].mixing_flops(c, SEQ) == 2.0 * win.heads * 2 * win.head_dim * band / SEQ
    assert MIXERS["gqa"].mixing_flops(c, SEQ) == 2.0 * full.heads * 2 * full.head_dim * (SEQ + 1) / 2
    assert MIXERS["gqa"].matmul_params(c) == c.hidden * (16 * (2 * 4 + 2 * 2) + 4)
    assert train_flops_per_token(c, SEQ) > 0
