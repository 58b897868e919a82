"""The decoder whose router reads the block's input ahead of attention
(``models/moe.py``: the router's input apart from the experts', ReGLU,
``act_zero``; ``models/gqa.py``: a kind with no rope, a window of several key
blocks; ``models/llama.py::_block``: what the MLP kind takes before the mixer
runs) against its plain reference
(``benchmark/reference/prerouted_moe_decoder.py``) on seeded weights, in
float32 on the CPU with the Pallas kernels interpreted, at a size where the
window (5) drops keys."""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import prerouted_moe_decoder as ref
from ray_tpu.models import PRESETS, init_params, loss_fn
from ray_tpu.models import gqa, moe
from ray_tpu.models.llama import DENSE, LEAD_DENSE, LlamaConfig, MIXERS, train_flops_per_token
from ray_tpu.ops import rms_norm
from ray_tpu.ops.attention import flash_attention, mha_reference

CFG = dataclasses.replace(PRESETS["prerouted-debug"], dtype=jnp.float32, remat_policy="attn")
SEQ = 32


def arch_of(c) -> dict:
    spec = lambda a: dict(heads=a.heads, kv_heads=a.kv_heads, head_dim=a.head_dim,  # noqa: E731
                          rope_theta=a.rope_theta, window=a.window)
    return {"kinds": {"gqa": spec(c.gqa), "gqa_win": spec(c.gqa_window)},
            "pattern": c.layer_pattern, "lead_pattern": (), "norm_eps": c.norm_eps,
            "top_k": c.moe_top_k, "held_first": c.moe_held[0] if c.moe_held else 0}


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
    """(the experts' normed input, the block's input the router reads): two
    tensors that differ."""
    return (jax.random.normal(jax.random.PRNGKey(5), (SEQ, CFG.hidden)),
            jax.random.normal(jax.random.PRNGKey(6), (SEQ, CFG.hidden)))


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def layer_of(params, slot):
    return jax.tree.map(lambda a: a[0], params["layers"][slot])


def fp8(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), tree)


def test_the_gates_two_ways_are_equal():
    """Top-6 of the LOGITS then softmax over the six (the published order, the
    reference's) and softmax over all 64, top-6, renormalised (``route``'s)."""
    tokens = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    router = jax.random.normal(jax.random.PRNGKey(4), (64, 64)) * 0.3
    r = moe.route(tokens, router, top_k=6, norm_topk=True)
    gates, chosen = ref.gates_of(jnp.einsum("nd,dx->nx", tokens, router,
                                            precision=jax.lax.Precision.HIGHEST), 6)
    assert np.abs(np.asarray(r["gates"]) - np.asarray(gates)).max() < 1e-6
    assert abs(float(gates.sum(-1).min()) - 1.0) < 1e-6
    # the same experts: the sort's rows per expert are the reference's choices'
    assert np.array_equal(np.asarray(r["sizes"]),
                          np.bincount(np.asarray(chosen).ravel(), minlength=64))
    # not renormalised, the gates are the softmax's own and sum to less
    plain = moe.route(tokens, router, top_k=6, norm_topk=False)
    assert float(plain["gates"].sum(-1).mean()) < 0.9


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_gates_activation_is_written_in_one_place(activation):
    gate = jnp.linspace(-3, 3, 13, dtype=jnp.bfloat16)
    got = moe.gate_act(gate, activation)
    want = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation](gate.astype(jnp.float32))
    assert got.dtype == jnp.float32 and np.array_equal(np.asarray(got), np.asarray(want))
    # what a count of zeroed products needs: only ReLU has a zero set
    counted = moe._zeroed(gate[None], None, activation)
    assert counted == ({} if activation == "silu" else {"zeroed": 7.0})


@pytest.mark.parametrize("field,value", [("moe_router_input", "attn_norm"),
                                         ("moe_activation", "gelu")])
def test_a_config_refuses_a_router_input_or_an_activation_it_does_not_know(field, value):
    with pytest.raises(ValueError, match=field):
        LlamaConfig(**{field: value})


def test_only_a_router_that_reads_the_blocks_input_takes_anything_before_the_mixer(params):
    x = jnp.ones((1, SEQ, CFG.hidden))
    layer = layer_of(params, "slot0")
    assert DENSE.early is None and LEAD_DENSE.early is None
    assert moe.MOE.early(x, layer, config=dataclasses.replace(
        CFG, moe_router_input="mlp_norm")) is None
    early = moe.MOE.early(x, layer, config=CFG)
    assert early["gates"].shape == (SEQ, 3) and early["sizes"].shape == (8,)


def test_a_spec_with_no_rope_leaves_q_and_k_as_projected_and_a_roped_one_does_not():
    t = jax.random.normal(jax.random.PRNGKey(8), (1, 6, SEQ, 16))
    positions = jnp.arange(SEQ)
    assert gqa._rope(t, positions, CFG.gqa) is t
    turned = gqa._rope(t, positions, CFG.gqa_window)
    assert np.array_equal(np.asarray(turned[:, :, 0]), np.asarray(t[:, :, 0]))  # position 0
    assert rel(turned[:, :, 1:], t[:, :, 1:]) > 0.3
    # the reference's rope, written out for itself, is the program's
    assert rel(turned[0].swapaxes(0, 1), ref._rope(t[0].swapaxes(0, 1), 1e3)) < 1e-6


def test_a_windows_blocks_follow_the_window():
    assert gqa.window_blocks(512) == (512, 512) == gqa.window_blocks(5)
    assert gqa.window_blocks(4096) == (gqa.WIDE_WINDOW_BLOCK,) * 2
    assert gqa.WIDE_WINDOW_BLOCK in (512, 1024, 2048) and 4096 % gqa.WIDE_WINDOW_BLOCK == 0
    # grouped queries: a kv head's group is a tile's rows, in short query blocks
    assert gqa.window_blocks(512, 9) == gqa.window_blocks(5, 3) == gqa.FOLDED_NARROW_BLOCKS
    assert gqa.window_blocks(4096, 7) == gqa.FOLDED_WIDE_BLOCKS
    for block_q, block_k in (gqa.FOLDED_NARROW_BLOCKS, gqa.FOLDED_WIDE_BLOCKS):
        assert block_q <= 256 and 16384 % block_q == 0 == 16384 % block_k


@pytest.mark.parametrize("hq,hkv,window,block", [(6, 2, 72, 16), (7, 1, 72, 16), (28, 4, 40, 16),
                                                 (7, 1, None, 32)],
                         ids=["win_3to1_band5", "win_7to1_band5", "win_28to4_band3",
                              "full_7to1"])
def test_attention_kernels_at_a_band_of_several_blocks_match_mha_reference(
        hq, hkv, window, block):
    """``attn_win_*`` where a query block's band is several key blocks, most of
    them whole (72 keys over blocks of 16: five or six), at three and at seven
    query heads a kv head; values and all three gradients (dK and dV summed
    over a kv head's group)."""
    key = jax.random.PRNGKey(3)
    b, s, d = 1, 128, 32
    q = jax.random.normal(key, (b, hq, s, d))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, hkv, s, d)) for i in (1, 2))
    got = jax.jit(lambda *x: flash_attention(*x, block_q=block, block_k=block, window=window))
    want = jax.jit(lambda *x: mha_reference(*x, window=window))
    assert rel(got(q, k, v), want(q, k, v)) < 1e-5
    g = jax.jit(jax.grad(lambda *x: jnp.sum(got(*x) ** 2), (0, 1, 2)))(q, k, v)
    w = jax.jit(jax.grad(lambda *x: jnp.sum(want(*x) ** 2), (0, 1, 2)))(q, k, v)
    assert max(rel(a, b_) for a, b_ in zip(g, w)) < 1e-5


MIXER_LIMIT = 1e-5


@pytest.mark.parametrize("kind,fault", [
    ("gqa", None), ("gqa_win", None), ("gqa", "rope_on_full"), ("gqa_win", "no_rope_window"),
    ("gqa_win", "narrower_window"), ("gqa_win", "no_window"), ("gqa", "fp8_weights"),
    ("gqa_win", "fp8_weights")])
def test_each_mixer_kind_matches_the_reference_and_a_planted_fault_does_not(
        params, hidden, kind, fault):
    slot = "slot0" if kind == "gqa" else "slot1"
    spec = CFG.gqa if kind == "gqa" else CFG.gqa_window
    layer = layer_of(params, slot)
    program_layer = fp8(layer) if fault == "fp8_weights" else layer
    spec = {None: spec, "fp8_weights": spec,
            "rope_on_full": dataclasses.replace(spec, rope_theta=1e3),
            "no_rope_window": dataclasses.replace(spec, rope_theta=0.0),
            "narrower_window": dataclasses.replace(spec, window=3),
            "no_window": dataclasses.replace(spec, window=1 << 30)}[fault]
    positions = jnp.arange(SEQ, dtype=jnp.int32)
    got, aux = jax.jit(lambda h, w: gqa.gqa_mixer(
        h[None], w, spec, config=CFG, positions=positions))(hidden[0], program_layer)
    want = jax.jit(lambda h, w: ref.gqa_mixer(h, w, ARCH["kinds"][kind]))(hidden[0], layer)
    if fault is None:
        assert rel(got[0], want) < MIXER_LIMIT
        assert ("window_share" in aux) == (kind == "gqa_win")
        if kind == "gqa_win":
            assert abs(float(aux["window_share"]) - ref.window_share(SEQ, 5)) < 1e-6
    else:
        assert rel(got[0], want) > 1000 * MIXER_LIMIT


LAYER_LIMIT = 1e-5


@pytest.mark.parametrize("fault", [None, "silu_experts", "router_after_attention",
                                   "router_normed", "gates_not_renormalised",
                                   "router_over_held", "fp8_weights"])
def test_the_expert_layer_matches_the_reference_and_a_planted_fault_does_not(
        params, hidden, fault):
    """The MLP kind through its own two hooks, the router reading a tensor that
    is not the experts': ``early`` on the block's input, ``apply`` on the
    normed one."""
    h, x_in = hidden
    layer = layer_of(params, "slot1")
    c, program_layer = CFG, layer
    if fault == "silu_experts":
        c = dataclasses.replace(CFG, moe_activation="silu")
    if fault == "router_after_attention":
        c = dataclasses.replace(CFG, moe_router_input="mlp_norm")
    if fault == "gates_not_renormalised":
        c = dataclasses.replace(CFG, moe_norm_topk=False)
    if fault == "router_over_held":
        c = dataclasses.replace(CFG, moe_experts=2, moe_held=None, moe_top_k=2)
        program_layer = {**layer, "router": layer["router"][:, :2]}
    if fault == "fp8_weights":
        program_layer = fp8(layer)

    def program(h, x_in, w):
        if fault == "router_normed":
            x_in = rms_norm(x_in, w["attn_norm"], eps=c.norm_eps)
        early = moe.MOE.early(x_in[None], w, config=c)
        return moe.MOE.apply(h[None], w, config=c, **({} if early is None else {"early": early}))

    got, aux = jax.jit(program)(h, x_in, program_layer)
    want, routing = jax.jit(lambda h, x_in, w: ref.expert_layer(h, x_in, w, top_k=3, first=0))(
        h, x_in, layer)
    if fault is not None:
        assert rel(got[0], want) > 1000 * LAYER_LIMIT
        return
    assert rel(got[0], want) < LAYER_LIMIT
    assert int(aux["dropped"]) == 0 and int(aux["rows"].sum()) == SEQ * 3
    assert np.array_equal(np.asarray(aux["rows"]), np.asarray(routing["rows"]))
    assert np.array_equal(np.asarray(aux["rows_held"]), np.asarray(routing["rows"][:2]))
    # ``act_zero`` against a count by hand: the held experts' gate products of
    # the tokens routed to them, the elements ReLU cuts
    chosen, cut, rows_held = np.asarray(routing["chosen"]), 0, 0
    for e in range(2):
        mine = (chosen == e).any(axis=-1)
        gate = np.asarray(h)[mine] @ np.asarray(layer["w_gate"][e])
        cut, rows_held = cut + int((gate <= 0).sum()), rows_held + int(mine.sum())
    assert rows_held == int(aux["rows_held"].sum()) and rows_held > 0
    assert abs(float(aux["act_zero"]) - cut / (rows_held * CFG.intermediate)) < 1e-6
    assert 0.3 < float(aux["act_zero"]) < 0.7


def test_the_held_ranges_adds_take_rows_of_20_lane_tiles():
    """``moe_rows`` at a width of 2,560: a row's 20 lane tiles are no whole
    number of sublane tiles, so the kernel fetches 24 and lays out 20."""
    from ray_tpu.ops.moe_rows import sum_rows

    key = jax.random.PRNGKey(9)
    src = jax.random.normal(key, (96, 2560))
    ids = jax.random.randint(jax.random.fold_in(key, 1), (96,), -1, 32)
    got = jax.jit(lambda s, i: sum_rows(s, i, 32))(src, ids)
    want = jnp.zeros((33, 2560)).at[jnp.where(ids >= 0, ids, 32)].add(src)[:32]
    assert got.shape == (32, 2560) and rel(got, want) < 1e-6


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(hidden):
    """Four chips hold two experts each of eight: what each share's held
    experts add is, summed, the uncut reference's layer (there is no shared
    expert to count once); the program's share equals the reference's."""
    h, x_in = hidden
    whole_cfg = dataclasses.replace(CFG, moe_held=None)
    whole = jax.tree.map(lambda a: a[0], jax.jit(lambda key: init_params(whole_cfg, key)[
        "layers"]["slot0"])(jax.random.PRNGKey(7)))
    layer_of_share = jax.jit(lambda h, x_in, w, first: ref.expert_layer(
        h, x_in, w, top_k=3, first=first)[0], static_argnums=3)
    want = layer_of_share(h, x_in, whole, 0)
    experts = ("w_gate", "w_up", "w_down")
    total = jnp.zeros_like(want)
    for first in range(0, 8, 2):
        share = {**whole, **{k: whole[k][first:first + 2] for k in experts}}
        part = layer_of_share(h, x_in, share, first)
        total = total + part
        if first == 4:  # one share through the program too: its range starts past 0
            c = dataclasses.replace(CFG, moe_held=(first, 2))
            got, _ = jax.jit(lambda h, x_in, w: moe.MOE.apply(
                h[None], w, config=c, early=moe.MOE.early(x_in[None], w, config=c)))(
                h, x_in, share)
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


def test_loss_counters_and_every_gradient_match_the_reference(
        params, rows, program_step, reference_step):
    (loss, aux), grads = program_step(params, rows)
    (want_loss, seen), want_grads = reference_step
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(aux["load_balance"]) - float(seen["balance"])) < 1e-5
    assert np.array_equal(np.asarray(aux["rows_per_expert"]), np.asarray(seen["rows_per_expert"]))
    assert abs(float(aux["attn_window_share"]) - ref.window_share(SEQ, 5)) < 1e-6
    assert int(aux["rows_dropped"]) == 0
    assert aux["act_zero"].shape == (4,) and aux["held_share"].shape == (4,)
    assert (np.asarray(aux["act_zero"]) > 0.3).all() and (np.asarray(aux["act_zero"]) < 0.7).all()
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0])
                       for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path, g in flat.items():
        assert rel(g, want_flat[path]) < 2e-4, jax.tree_util.keystr(path)


def test_the_references_gradient_by_hand_is_jax_grad_of_its_own_loss(
        params, rows, reference_step):
    """``loss_and_grads``, a block at a time with the head in chunks of
    positions, against ``jax.grad`` of ``loss``."""
    (want_loss, seen), want_grads = reference_step
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    by_hand_loss, by_hand_seen, by_hand = ref.loss_and_grads(
        params, rows, ARCH, aux_weight=CFG.moe_aux_weight)
    assert abs(by_hand_loss - float(want_loss)) < 1e-5
    assert abs(by_hand_seen["balance"] - float(seen["balance"])) < 1e-6
    assert np.allclose(by_hand_seen["logits"], np.asarray(seen["logits"]), atol=1e-5)
    assert {jax.tree_util.keystr(p) for p in want_flat} == set(by_hand)
    for path, g in want_flat.items():
        assert rel(by_hand[jax.tree_util.keystr(path)], g) < 1e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault", ["router_after_attention", "silu_experts"])
def test_a_fault_planted_in_the_whole_stack_moves_the_loss_and_the_gradients(
        params, rows, reference_step, fault):
    c = dataclasses.replace(CFG, **{"router_after_attention": {"moe_router_input": "mlp_norm"},
                                    "silu_experts": {"moe_activation": "silu"}}[fault])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": rows}, c, chunk_tokens=16, return_aux=True),
        has_aux=True))(params)
    (want_loss, _), want_grads = reference_step
    assert abs(float(loss) - float(want_loss)) > 1e-3
    worst = max(rel(g, w) for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    assert worst > 0.1
    assert ("act_zero" in aux) == (fault != "silu_experts")


def test_remat_attn_runs_each_attention_forward_and_the_routing_once():
    c = CFG
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16)))(shapes))
    # the scanned period: one full layer and one window layer
    assert text.count("name=flash_fwd") == 1 and text.count("name=attn_win_fwd") == 1
    assert text.count("name=flash_bwd_dq") == 1 and text.count("name=attn_win_bwd_dkdv") == 1
    # the routing is made once a layer, from the block's input, and saved: two
    # sorts a layer of the period and none again in the backward pass, which
    # takes only the top-k of the saved probabilities again (as every routed
    # model here: the chosen ids are not among ``ROUTE_NAMES``)
    assert text.count("argsort") == 4 and text.count("top_k[") == 4
    # the kinds count their own FLOPs: the full kind the triangle's keys, the
    # window kind its band's, the expert layer its held share in expectation
    band = 5 * (5 + 1) / 2 + (SEQ - 5) * 5
    assert MIXERS["gqa_win"].mixing_flops(c, SEQ) == 2.0 * 6 * 2 * 16 * band / SEQ
    assert MIXERS["gqa"].mixing_flops(c, SEQ) == 2.0 * 6 * 2 * 16 * (SEQ + 1) / 2
    assert MIXERS["gqa"].matmul_params(c) == c.hidden * 16 * (2 * 6 + 2 * 2)
    assert moe.MOE.matmul_params(c) == c.hidden * 8 + 3 * (2 / 8) * 3 * c.hidden * 32
    assert train_flops_per_token(c, SEQ) > 0
