"""What remat ``attn`` saves of the latent kinds' blocks, by name (PR 52): the
stream as the mixer's output joins it (``post_attn``) for ``mla`` and
``mla_win``, not for ``mla_full``, and the step of a kind that does not list
it did not move. Each case traces a block or a step of its own; the whole
stack against its reference is ``tests/test_latent_full_model.py``'s, whose
names these cases share."""

import dataclasses

import jax
import jax.numpy as jnp
import jaxpr_walk
import numpy as np
import pytest

from test_latent_full_model import rel
from ray_tpu.models import PRESETS, init_params, loss_fn
from ray_tpu.models.kinds import POST_ATTN
from ray_tpu.models.mla import LATENT_NAMES, SAVE_NAMES


@pytest.mark.parametrize("preset,kind,field,slot,saves", [
    ("latent-sparse-debug", "mla", "mla", "slot0", True),
    ("latent-sparse-debug", "mla_win", "mla_window", "slot1", True),
    ("latent-full-debug", "mla_full", "mla_full", None, False)])
def test_remat_attn_runs_the_output_product_once_where_the_kind_saves_the_stream_it_joins(
        monkeypatch, preset, kind, field, slot, saves):
    """One block of each latent kind under remat ``attn``, differentiated.
    ``mla`` and ``mla_win`` save ``post_attn``, the stream as the mixer's
    output joins it, so ``wo``'s forward-shaped product ([B, H, S, dv] x
    [H, dv, E]) is in the program once; ``mla_full`` does not (Kimi-K2's step
    has no room: ``models/mla.py``) and its second run holds the product
    again. The kind's tuple alone decides: given the other tuple, each kind
    reads as the other. The gradients are those of the block under no remat."""
    import functools

    from ray_tpu.models.llama import MIXERS, _apply_remat, _block

    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn")
    a, s = getattr(c, field), 48
    params = jax.jit(lambda key: init_params(c, key))(jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda w: w[0], params["layers"][slot] if slot else params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, c.hidden))

    def grads(config):
        block = _apply_remat(functools.partial(
            _block, positions=jnp.arange(s, dtype=jnp.int32), config=config, mesh=None,
            mixer=kind), config, kind)

        def loss(x, layer):
            y, _, counted = block(x, layer)
            return jnp.sum(y * y) + counted.get("index_loss", 0.0)

        return jax.grad(loss, argnums=(0, 1))

    def output_products():
        equations = list(jaxpr_walk.equations(jax.make_jaxpr(grads(c))(x, layer).jaxpr))
        assert POST_ATTN in [e.params["name"] for e in equations if e.primitive.name == "name"]
        return len(jaxpr_walk.products(
            equations, (1, a.heads, s, a.v_dim), (a.heads, a.v_dim, c.hidden)))

    assert MIXERS[kind].save_names == (SAVE_NAMES if saves else LATENT_NAMES)
    assert output_products() == (1 if saves else 2)
    got = jax.jit(grads(c))(x, layer)
    want = jax.jit(grads(dataclasses.replace(c, remat=False)))(x, layer)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (rel(g, w) < 1e-5) if np.asarray(w).any() else not np.asarray(g).any()
    monkeypatch.setitem(MIXERS, kind, dataclasses.replace(
        MIXERS[kind], save_names=LATENT_NAMES if saves else SAVE_NAMES))
    assert output_products() == (2 if saves else 1)


def test_the_kind_that_attends_every_key_keeps_the_names_it_had_and_the_two_others_the_stream():
    """The split of the latent kinds' saved names is the chip compiler's: with
    ``post_attn`` saved Kimi-K2's step (``mla_full``, 1 x 4,096, five layers)
    counts 16,799,216,640 bytes of the chip's 16,909,336,064, past the
    16.60e9 ISSUE 52 set before the count (16,270,250,496 without it), and the
    sparse cell's (``mla``, ``mla_win``, 2 x 8,192) 11,956,870,656 (+235 MB).
    Whoever gives ``mla_full`` the stream brings a new count of that step
    (``tests/test_chip_compile.py``'s slow test is the sparse step's)."""
    from ray_tpu.models.llama import MIXERS

    assert POST_ATTN not in LATENT_NAMES and SAVE_NAMES == LATENT_NAMES + (POST_ATTN,)
    assert MIXERS["mla_full"].save_names == LATENT_NAMES
    assert MIXERS["mla"].save_names == MIXERS["mla_win"].save_names == SAVE_NAMES


@pytest.mark.parametrize("preset", ["hybrid-debug", "debug"])
def test_the_name_on_the_stream_leaves_the_other_kinds_steps_as_they_were(monkeypatch, preset):
    """A DeltaNet and the plain ``attn`` stack, differentiated under remat
    ``attn``: no kind of theirs saves ``post_attn``, and the step is, equation
    for equation (primitive and the shapes in and out), the step traced with
    the name not given, plus the name's own equations. (The grouped-query
    kinds by spec save it since PR 59: ``tests/test_gqa_remat_names.py``.)"""
    from ray_tpu.models import llama

    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn")
    assert not any(POST_ATTN in llama.MIXERS[kind].save_names
                   for kind in c.layer_pattern + c.lead_pattern)
    tokens = jnp.zeros((1, 64), jnp.int32)  # the delta rule's rows: a multiple of 64
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))

    def traced():
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16)))(shapes)
        return [(e.primitive.name, [str(v.aval) for v in e.invars], [str(v.aval) for v in e.outvars])
                for e in jaxpr_walk.equations(jaxpr.jaxpr)
                if not (e.primitive.name == "name" and e.params["name"] == POST_ATTN)]

    named = traced()
    given = llama.checkpoint_name
    monkeypatch.setattr(llama, "checkpoint_name",
                        lambda x, name: x if name == POST_ATTN else given(x, name))
    assert named == traced()
