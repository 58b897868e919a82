"""The latent-attention decoder with a learned selection of keys
(``models/mla.py``, ``ops/sparse_index.py``, the sigmoid router of
``models/moe.py``, leading layers of ``models/llama.py``) against its plain
reference (``benchmark/reference/latent_sparse_decoder.py``) on seeded
weights, in float32 on the CPU with the Pallas kernels interpreted, at a size
where the window (5) and the selection (top-4) both drop keys. The kernels
under it alone: ``tests/test_latent_sparse_kernels.py``."""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import jaxpr_walk

from benchmark.reference import latent_sparse_decoder as ref
from ray_tpu.models import PRESETS, init_params, loss_fn, update_buffers
from ray_tpu.models.kinds import POST_ATTN
from ray_tpu.models.llama import MIXERS, _loss, forward, train_flops_per_token
from ray_tpu.models.mla import LATENT_NAMES, SAVE_NAMES, mla_mixer
from ray_tpu.models.moe import moe_block

CFG = dataclasses.replace(PRESETS["latent-sparse-debug"], dtype=jnp.float32,
                          remat_policy="attn")
SEQ = 32
INDEX_LEAVES = ("w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw")


def arch_of(c) -> dict:
    return {"kinds": {"mla": dataclasses.asdict(c.mla),
                      "mla_win": dataclasses.asdict(c.mla_window)},
            "pattern": c.layer_pattern, "lead_pattern": c.lead_pattern,
            "norm_eps": c.norm_eps, "top_k": c.moe_top_k, "norm_topk": c.moe_norm_topk,
            "held_first": c.moe_held[0] if c.moe_held else 0}


@pytest.fixture(scope="module")
def params():
    def move(path, leaf):  # norms off 1, the bias off 0: a fault must show
        name = str(getattr(path[-1], "key", ""))
        # crc32 and not ``hash``, which differs from one process to the next
        key = jax.random.fold_in(jax.random.PRNGKey(1),
                                 zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm"):
            return leaf + jax.random.uniform(key, leaf.shape, minval=-0.5, maxval=0.5)
        if name in ("router_bias", "ik_bias"):
            return leaf + 0.1 * jax.random.normal(key, leaf.shape)
        return leaf

    # one program: made an op at a time the draws take 18 s
    return jax.jit(lambda: jax.tree_util.tree_map_with_path(
        move, init_params(CFG, jax.random.PRNGKey(0))))()


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, CFG.vocab_size)


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))



def step_equations(params):
    """Every equation of the differentiated, remat-ed stack (a leading full
    layer and a scanned period of a full and three window ones) on 1 x 48
    tokens."""
    assert CFG.remat_policy == "attn"
    tokens = jnp.zeros((1, 48), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=16)))(params)
    return list(jaxpr_walk.equations(jaxpr.jaxpr))


def test_the_indexers_loss_is_one_kernel_each_way_and_none_again_under_remat(params):
    """The differentiated, remat-ed stack (a leading full layer and a scanned
    one): the forward kernel once a full layer, the backward kernel once, no
    second run of the forward under remat (its statistics are saved by
    name), and the KL's exponentials and logarithms nowhere in XLA."""
    assert {"dsa_kl_z", "dsa_kl_lse"} <= set(MIXERS["mla"].save_names)
    equations = step_equations(params)
    kernels = [str(e.params["name"]) for e in equations if e.primitive.name == "pallas_call"]
    assert kernels.count("dsa_probs") == 2 and kernels.count("dsa_probs_bwd") == 2
    # what else an indexed layer runs, for scale: the scores again under remat
    assert kernels.count("dsa_index_fwd") == 4 and kernels.count("attn_sel_fwd") == 2
    named = {e.params["name"] for e in equations if e.primitive.name == "name"}
    assert {"dsa_kl_z", "dsa_kl_lse", "dsa_mask"} <= named
    square = [e.primitive.name for e in equations
              if any(getattr(v.aval, "shape", None) == (1, 48, 48) for v in e.invars)]
    assert square and not {"exp", "log", "exp2", "log1p", "logistic"} & set(square), square


def test_the_output_product_runs_once_a_layer_body_under_remat(params, monkeypatch):
    """The same stack: ``wo``'s forward-shaped product ([1, H, 48, 16] x
    [H, 16, 64]: 4 heads in a full layer, 2 in a window one) once in each of
    the five layer bodies (the leading layer's and the period's four): the
    stream it joins is a saved name (``post_attn``); with the name out of the
    kinds' ``save_names`` it runs again in every body, and those five are all
    the products there are more of."""
    full, window = CFG.mla, CFG.mla_window

    def wo(equations, a):
        return len(jaxpr_walk.products(equations, (1, a.heads, 48, a.v_dim),
                                       (a.heads, a.v_dim, CFG.hidden)))

    def dots(equations):
        return sum(e.primitive.name == "dot_general" for e in equations)

    saved = step_equations(params)
    assert [e.params["name"] for e in saved if e.primitive.name == "name"].count(POST_ATTN) == 5
    assert (wo(saved, full), wo(saved, window), dots(saved)) == (2, 3, 207)
    for kind in ("mla", "mla_win"):
        assert MIXERS[kind].save_names == SAVE_NAMES == LATENT_NAMES + (POST_ATTN,)
        monkeypatch.setitem(MIXERS, kind,
                            dataclasses.replace(MIXERS[kind], save_names=LATENT_NAMES))
    again = step_equations(params)
    assert (wo(again, full), wo(again, window), dots(again)) == (4, 6, 212)


@pytest.mark.parametrize("kind", ["mla", "mla_win"])
def test_each_mixer_kind_matches_the_reference(params, kind):
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot0" if kind == "mla" else "slot1"])
    h = jax.random.normal(jax.random.PRNGKey(6), (SEQ, CFG.hidden))
    spec = CFG.mla if kind == "mla" else CFG.mla_window
    mixer = jax.jit(lambda h, layer, spec, seen: mla_mixer(
        h[None], layer, spec, config=CFG, positions=jnp.arange(SEQ), return_selection=seen),
        static_argnums=(2, 3))  # a program a spec, not an op at a time
    got, aux = mixer(h, layer, spec, True)
    want, seen = ref.mla_mixer(h, layer, dataclasses.asdict(spec), CFG.norm_eps)
    assert rel(got[0], want) < 1e-5
    if kind == "mla":
        assert np.array_equal(np.asarray(aux["selection"][0], bool), np.asarray(seen["selection"]))
        # the selection drops keys: 4 of up to 32
        assert float(aux["selected_share"]) < 0.3
        assert abs(float(aux["index_loss"]) - float(seen["index_loss"])) < 1e-5
    # the faults a comparison must see, each well above rounding
    for fault in ({"rescale": False}, {"gate": False},
                  {"window": 4} if kind == "mla_win" else {"index_top_k": 3}):
        bad, _ = mixer(h, layer, dataclasses.replace(spec, **fault), False)
        assert rel(bad[0], want) > 0.02, fault


def test_expert_layer_matches_the_reference(params):
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot1"])
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, CFG.hidden))
    block = jax.jit(lambda h, layer, score: moe_block(
        h, layer, top_k=CFG.moe_top_k, norm_topk=True, held=CFG.moe_held, score=score),
        static_argnums=2)
    got, aux = block(h, layer, "sigmoid")
    balances = []
    for i in range(2):
        want, routing = ref.expert_layer(h[i], layer, top_k=CFG.moe_top_k, norm_topk=True)
        assert rel(got[i], want) < 1e-5
        balances.append(routing["balance"])
    assert abs(float(aux["load_balance"]) - float(jnp.mean(jnp.stack(balances)))) < 1e-6
    assert int(aux["dropped"]) == 0
    # a softmax router, or the bias left out of the choice, is another layer
    soft, _ = block(h, layer, "softmax")
    unbiased, _ = block(h, {k: v for k, v in layer.items() if k != "router_bias"}, "sigmoid")
    want = jnp.stack([ref.expert_layer(h[i], layer, top_k=CFG.moe_top_k, norm_topk=True)[0]
                      for i in range(2)])
    assert rel(soft, want) > 0.02 and rel(unbiased, want) > 0.02


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """4 chips hold 2 of 8 experts each: their parts of the routed sum, the
    shared expert counted once, are the uncut reference's layer."""
    whole_cfg = dataclasses.replace(CFG, moe_held=None)
    layer = jax.tree.map(lambda a: a[0], init_params(whole_cfg, jax.random.PRNGKey(8))
                         ["layers"]["slot1"])
    layer["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    h = jax.random.normal(jax.random.PRNGKey(10), (SEQ, CFG.hidden))
    want, _ = ref.expert_layer(h, layer, top_k=CFG.moe_top_k, norm_topk=True)
    shared = ref._swiglu(h, layer["w_shared_gate"], layer["w_shared_up"],
                         layer["w_shared_down"])
    total, rows = 0.0, 0
    for first in range(0, 8, 2):
        share = {k: (v[first:first + 2] if k in ("w_gate", "w_up", "w_down") else v)
                 for k, v in layer.items()}
        part, aux = moe_block(h[None], share, top_k=CFG.moe_top_k, norm_topk=True,
                              held=(first, 2), score="sigmoid")
        total = total + (part[0] - shared)
        rows += int(aux["rows_held"].sum())
    assert rows == SEQ * CFG.moe_top_k
    assert rel(total + shared, want) < 1e-5


@pytest.fixture(scope="module")
def program_step(params, rows):
    """One compiled pass of the program: its logits, its loss with everything
    counted beside it, and the gradients of the loss's two parts apart (the
    model's terms; the indexer's), which add up to the loss's."""
    def parts(p):
        # the loss's undecorated body: ``loss_fn``'s ``aux`` comes from the forward
        # pass and takes no cotangent (``tests/test_device_passes.py`` holds the
        # two to one program)
        loss, aux = _loss(p, {"tokens": rows}, CFG, mesh=None, chunk_tokens=16)
        index = aux["index_loss"]
        return jnp.stack([loss - index, index]), (loss, aux)

    jac, (loss, aux) = jax.jit(jax.jacrev(parts, has_aux=True))(params)
    return {"logits": jax.jit(lambda p: forward(p, rows, CFG))(params), "loss": loss,
            "aux": aux, "model_grads": jax.tree.map(lambda a: a[0], jac),
            "index_grads": jax.tree.map(lambda a: a[1], jac)}


def test_the_stack_its_loss_and_every_gradient_match_the_reference(params, rows, program_step):
    arch = arch_of(CFG)
    got, aux = program_step["loss"], program_step["aux"]
    got_g = jax.tree.map(jnp.add, program_step["model_grads"], program_step["index_grads"])
    (want, seen), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, rows, arch, aux_weight=CFG.moe_aux_weight, return_seen=True),
        has_aux=True))(params)
    assert rel(program_step["logits"][0], seen["logits"]) < 1e-5
    assert abs(float(got) - float(want)) < 1e-5
    assert abs(float(aux["ce"]) - float(seen["ce"])) < 1e-5
    assert abs(float(aux["load_balance"]) - float(seen["balance"])) < 1e-6
    assert abs(float(aux["index_loss"]) - float(seen["index_loss"])) < 1e-5
    assert abs(float(aux["attn_selected_share"]) - float(seen["selected_share"])) < 1e-6
    assert np.array_equal(np.asarray(aux["rows_per_expert"]), np.asarray(seen["rows_per_expert"]))
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(want_g)[0]]
    for name, a, b in zip(names, jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        if name.endswith("['router_bias']"):
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
        else:
            assert rel(a, b) < 2e-4, (name, rel(a, b))


def test_the_two_losses_touch_disjoint_leaves(program_step):
    """The model's loss reaches no indexer leaf; the indexer's reaches no other."""
    for what in ("model", "index"):
        for path, leaf in jax.tree_util.tree_flatten_with_path(program_step[what + "_grads"])[0]:
            name = str(path[-1].key)
            moved = bool(np.asarray(leaf).any())
            if name == "router_bias":
                assert not moved
            else:
                assert moved == ((name in INDEX_LEAVES) == (what == "index")), (
                    what, jax.tree_util.keystr(path))


def test_the_bias_steps_from_the_counts_and_the_optimizer_leaves_it(params, program_step):
    aux = program_step["aux"]
    g = jax.tree.map(jnp.add, program_step["model_grads"], program_step["index_grads"])
    opt = optax.adafactor(1e-3)
    updates, _ = jax.jit(opt.update)(g, opt.init(params), params)  # one program, not an op a leaf
    after = update_buffers(optax.apply_updates(params, updates), aux, CFG)
    counts = np.asarray(aux["rows_per_expert"]).reshape(1, 4, 8)
    for i in range(4):
        before = params["layers"][f"slot{i}"]["router_bias"]
        assert not np.asarray(updates["layers"][f"slot{i}"]["router_bias"]).any()
        want = ref.bias_after(before, counts[:, i], CFG.moe_bias_rate)
        assert np.allclose(after["layers"][f"slot{i}"]["router_bias"], want, atol=1e-7)
        assert np.abs(np.asarray(want - before)).max() == pytest.approx(
            CFG.moe_bias_rate, rel=1e-4)
    # a dense model has no such leaf and comes back as it went
    p = {"layers": {}}
    assert update_buffers(p, {}, PRESETS["debug"]) is p


def test_flops_count_the_kept_keys_and_the_leading_layer():
    c = CFG
    with_lead = train_flops_per_token(c, SEQ)
    without = train_flops_per_token(dataclasses.replace(c, lead_pattern=(), n_layers=4), SEQ)
    lead = MIXERS["mla"]
    assert with_lead - without == pytest.approx(
        6 * (lead.matmul_params(c) + 3 * c.hidden * c.lead_intermediate)
        + 3 * lead.mixing_flops(c, SEQ))
    # top-4 of 32: a query keeps min(t + 1, 4) keys
    kept = sum(min(t + 1, 4) for t in range(SEQ)) / SEQ
    a = c.mla
    assert lead.mixing_flops(c, SEQ) == pytest.approx(
        2 * a.heads * (a.qk_dim + a.v_dim) * kept
        + 2 * a.index_heads * a.index_dim * (SEQ + 1) / 2)
