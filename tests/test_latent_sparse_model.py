"""The latent-attention decoder with a learned selection of keys
(``models/mla.py``, ``ops/sparse_index.py``, the sigmoid router of
``models/moe.py``, leading layers of ``models/llama.py``) against its plain
reference (``benchmark/reference/latent_sparse_decoder.py``) on seeded
weights, in float32 on the CPU with the Pallas kernels interpreted, at a size
where the window (5) and the selection (top-4) both drop keys."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import jaxpr_walk

from benchmark.reference import latent_sparse_decoder as ref
from ray_tpu.models import PRESETS, init_params, loss_fn, update_buffers
from ray_tpu.models.llama import MIXERS, forward, train_flops_per_token
from ray_tpu.models.mla import mla_mixer
from ray_tpu.models.moe import moe_block
from ray_tpu.ops import sparse_index
from ray_tpu.ops.attention import flash_attention, mha_reference

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
    p = init_params(CFG, jax.random.PRNGKey(0))

    def move(path, leaf):  # norms off 1, the bias off 0: a fault must show
        name = str(getattr(path[-1], "key", ""))
        key = jax.random.fold_in(jax.random.PRNGKey(1), hash(jax.tree_util.keystr(path)) % 2**31)
        if name.endswith("norm"):
            return leaf + jax.random.uniform(key, leaf.shape, minval=-0.5, maxval=0.5)
        if name in ("router_bias", "ik_bias"):
            return leaf + 0.1 * jax.random.normal(key, leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(move, p)


@pytest.fixture(scope="module")
def rows():
    return jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, CFG.vocab_size)


def rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


@pytest.mark.parametrize("kw", [dict(window=5), dict(window=40), dict(mask=True),
                                dict(mask=True, window=None)],
                         ids=["window5", "window40", "keyset", "keyset_again"])
@pytest.mark.parametrize("dims", [(48, 32), (64, 32)], ids=["192_128", "256_128"])
def test_attention_kernels_under_masks_match_mha_reference(kw, dims):
    d, dv = dims
    key = jax.random.PRNGKey(3)
    b, h, s = 2, 2, 128
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d)) for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, dv))
    kw = dict(kw)
    if kw.pop("mask", False):
        keep = jax.random.uniform(jax.random.fold_in(key, 3), (b, s, s)) < 0.3
        kw["mask"] = ((keep | jnp.eye(s, dtype=bool)) & jnp.tril(jnp.ones((s, s), bool))
                      ).astype(jnp.int8)
    got = lambda *x: flash_attention(*x, block_q=32, block_k=32, **kw)  # noqa: E731
    want = lambda *x: mha_reference(*x, **kw)  # noqa: E731
    assert rel(got(q, k, v), want(q, k, v)) < 1e-5
    g = jax.grad(lambda *x: jnp.sum(got(*x) ** 2), (0, 1, 2))(q, k, v)
    w = jax.grad(lambda *x: jnp.sum(want(*x) ** 2), (0, 1, 2))(q, k, v)
    assert max(rel(a, b_) for a, b_ in zip(g, w)) < 1e-5


def test_window_kernels_walk_only_the_band():
    from ray_tpu.ops.attention import _tile_walk

    # 8k rows in 512-blocks under a 513-wide window: two key blocks a query
    # block, two query blocks a key block, of sixteen (one in the first and
    # the last row: 31 tiles of 256, every one of them live)
    for key_major in (False, True):
        (q_blocks, k_blocks, first, last), steps = _tile_walk(
            16, 16, 512, 512, True, 513, key_major=key_major)
        rows = k_blocks if key_major else q_blocks
        assert np.bincount(rows).max() == 2 and (len(rows), len(rows)) == steps == (31, 31)
        assert (q_blocks - k_blocks).tolist() == [0] + [1, 0] * 15
        assert first.sum() == last.sum() == 16


def test_index_scores_and_selection_match_plain_jnp(monkeypatch):
    monkeypatch.setattr(sparse_index, "FWD_BLOCKS", (64, 128))
    monkeypatch.setattr(sparse_index, "BWD_BLOCKS", (32, 128))
    key = jax.random.PRNGKey(4)
    b, j, t, d = 2, 3, 256, 32
    q = jax.random.normal(key, (b, j, t, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, d))
    w = jax.random.normal(jax.random.fold_in(key, 2), (b, t, j))
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, t, t))
    want = sparse_index.index_scores_reference(q, k, w)
    assert rel(sparse_index.index_scores(q, k, w), want) < 1e-5
    got_g = jax.grad(lambda *x: jnp.sum(sparse_index.index_scores(*x) * g), (0, 1, 2))(q, k, w)
    want_g = jax.grad(lambda *x: jnp.sum(sparse_index.index_scores_reference(*x) * g),
                      (0, 1, 2))(q, k, w)
    assert max(rel(a, b_) for a, b_ in zip(got_g, want_g)) < 1e-5
    mask = sparse_index.select_top_k(want, 16)
    assert np.array_equal(np.asarray(mask[0], bool), np.asarray(ref.select(want[0], 16)))
    counts = np.asarray(mask.sum(-1))
    # (a row whose 16th score is an exact 0, every head's ReLU shut, keeps its ties)
    assert (counts[:, :16] == np.arange(1, 17)).all() and (counts[:, 16:] >= 16).all()
    assert np.median(counts[:, 16:]) == 16
    # a tie at the last place keeps every tied key
    tied = want.at[:, -1, :20].set(7.0).at[:, -1, 20:].set(0.0)
    assert int(sparse_index.select_top_k(tied, 16)[0, -1].sum()) == 20


KL_CASES = {
    # two batch rows in 128-blocks of 256: the statistics gather over two key
    # blocks, the tile above the diagonal is skipped
    "two_rows_two_key_blocks": dict(b=2, h=3, t=256, d=48, block=128, top_k=16),
    # every row shorter than top_k: every causal key is in its set
    "every_causal_key_kept": dict(b=1, h=2, t=128, d=16, block=128, top_k=300),
    "a_tie_at_the_threshold": dict(b=2, h=2, t=256, d=16, block=128, top_k=16, tie=True),
    # a key outside the set scores above its row's logsumexp (over the set)
    "a_key_above_the_logsumexp": dict(b=1, h=2, t=128, d=16, block=128, top_k=4, clamp=True),
    "one_block": dict(b=2, h=2, t=32, d=16, block=1024, top_k=4),
    # no block divides 200: index_loss of head_summed_probs_reference itself
    "a_length_no_block_fits": dict(b=1, h=2, t=200, d=16, block=1024, top_k=8, path="reference"),
}


@pytest.mark.parametrize("case", list(KL_CASES))
def test_index_kl_and_its_gradient_match_the_plain_form(case):
    """The loss made tile by tile against ``index_loss`` of the plain
    head-summed probabilities, and its gradient by the backward kernel
    against ``jax.grad`` of that form."""
    from ray_tpu.ops import trace_log

    c = KL_CASES[case]
    b, h, t, d, top_k = (c[x] for x in ("b", "h", "t", "d", "top_k"))
    key = jax.random.PRNGKey(5)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, h, t, d)) for i in (0, 1))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, 2.0 * jax.random.normal(jax.random.fold_in(key, 2), (b, t, t)), 0.0)
    if c.get("tie"):
        scores = scores.at[:, -1, :20].set(7.0).at[:, -1, 20:].set(-1.0)
    mask = sparse_index.select_top_k(scores, top_k)
    if c.get("tie"):
        assert int(mask[0, -1].sum()) == 20
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * 0.3
    kept = mask[:, None] != 0
    lse = jax.nn.logsumexp(jnp.where(kept, s, -jnp.inf), axis=-1)
    if c.get("clamp"):
        assert float(jnp.max(jnp.where(~kept & causal, s - lse[..., None], -jnp.inf))) > 1.0
    before = trace_log.kernel_traces().get("dsa_probs:" + c.get("path", "interpret"), 0)
    want, want_g = jax.value_and_grad(lambda x: sparse_index.index_loss(
        x, sparse_index.head_summed_probs_reference(q, k, lse, 0.3), mask))(scores)
    got, (*others, got_g) = jax.value_and_grad(lambda q, k, lse, x: sparse_index.index_kl(
        q, k, lse, x, mask, sm_scale=0.3, block=c["block"]), (0, 1, 2, 3))(q, k, lse, scores)
    assert trace_log.kernel_traces()["dsa_probs:" + c.get("path", "interpret")] > before
    assert float(want) > 0.01 and abs(float(got) - float(want)) < 1e-5 * float(want)
    assert rel(got_g, want_g) < 1e-5
    # nothing outside the key sets, and no cotangent but the scores'
    assert not np.asarray(jnp.where(mask != 0, 0.0, got_g)).any()
    assert not any(np.asarray(x).any() for x in others)


def test_the_indexers_loss_is_one_kernel_each_way_and_none_again_under_remat(params):
    """The differentiated, remat-ed stack (a leading full layer and a scanned
    one): the forward kernel once a full layer, the backward kernel once, no
    second run of the forward under remat (its statistics are saved by
    name), and the KL's exponentials and logarithms nowhere in XLA."""
    assert {"dsa_kl_z", "dsa_kl_lse"} <= set(MIXERS["mla"].save_names)
    assert CFG.remat_policy == "attn"
    tokens = jnp.zeros((1, 48), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, CFG, chunk_tokens=16)))(params)
    equations = list(jaxpr_walk.equations(jaxpr.jaxpr))
    kernels = [str(e.params["name"]) for e in equations if e.primitive.name == "pallas_call"]
    assert kernels.count("dsa_probs") == 2 and kernels.count("dsa_probs_bwd") == 2
    # what else an indexed layer runs, for scale: the scores again under remat
    assert kernels.count("dsa_index_fwd") == 4 and kernels.count("attn_sel_fwd") == 2
    named = {e.params["name"] for e in equations if e.primitive.name == "name"}
    assert {"dsa_kl_z", "dsa_kl_lse", "dsa_mask"} <= named
    square = [e.primitive.name for e in equations
              if any(getattr(v.aval, "shape", None) == (1, 48, 48) for v in e.invars)]
    assert square and not {"exp", "log", "exp2", "log1p", "logistic"} & set(square), square


@pytest.mark.parametrize("kind", ["mla", "mla_win"])
def test_each_mixer_kind_matches_the_reference(params, kind):
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot0" if kind == "mla" else "slot1"])
    h = jax.random.normal(jax.random.PRNGKey(6), (SEQ, CFG.hidden))
    spec = CFG.mla if kind == "mla" else CFG.mla_window
    got, aux = mla_mixer(h[None], layer, spec, config=CFG,
                         positions=jnp.arange(SEQ), return_selection=True)
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
        bad, _ = mla_mixer(h[None], layer, dataclasses.replace(spec, **fault), config=CFG,
                           positions=jnp.arange(SEQ))
        assert rel(bad[0], want) > 0.02, fault


def test_expert_layer_matches_the_reference(params):
    layer = jax.tree.map(lambda a: a[0], params["layers"]["slot1"])
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, CFG.hidden))
    got, aux = moe_block(h, layer, top_k=CFG.moe_top_k, norm_topk=True, held=CFG.moe_held,
                         score="sigmoid")
    balances = []
    for i in range(2):
        want, routing = ref.expert_layer(h[i], layer, top_k=CFG.moe_top_k, norm_topk=True)
        assert rel(got[i], want) < 1e-5
        balances.append(routing["balance"])
    assert abs(float(aux["load_balance"]) - float(jnp.mean(jnp.stack(balances)))) < 1e-6
    assert int(aux["dropped"]) == 0
    # a softmax router, or the bias left out of the choice, is another layer
    soft, _ = moe_block(h, layer, top_k=CFG.moe_top_k, norm_topk=True, held=CFG.moe_held)
    unbiased, _ = moe_block(h, {k: v for k, v in layer.items() if k != "router_bias"},
                            top_k=CFG.moe_top_k, norm_topk=True, held=CFG.moe_held,
                            score="sigmoid")
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
        loss, aux = loss_fn(p, {"tokens": rows}, CFG, chunk_tokens=16, return_aux=True)
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
    updates, _ = opt.update(g, opt.init(params), params)
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
