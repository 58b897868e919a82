"""What remat ``attn`` saves of the grouped-query kinds' blocks, by name (PR
59): the stream as the mixer's output joins it (``post_attn``) for ``gqa`` and
``gqa_win``, one tuple for both, as ``tests/test_latent_remat_names.py`` holds
of the latent kinds. Each case traces a whole differentiated step of its own
debug preset."""

import dataclasses

import jax
import jax.numpy as jnp
import jaxpr_walk
import numpy as np
import pytest

from test_latent_full_model import rel
from ray_tpu.models import PRESETS, gqa, init_params, loss_fn
from ray_tpu.models.kinds import POST_ATTN

SEQ = 64  # the state-space scan's rows: whole chunks of 16

# preset, the grouped-query layers its traced step holds (a period's slots are
# traced once, inside the scan over periods; a leading layer beside it)
PRESETS_OF_GROUPED_QUERIES = [
    # both kinds under a head-wise gate, and a leading ``gqa`` layer
    ("window-moe-debug", 5),
    # an un-roped ``gqa`` layer, the router ahead of attention
    ("prerouted-debug", 2),
    # one ``gqa`` layer under a published softmax scale among ``mamba2``
    ("granite-hybrid-debug", 1),
]


def _config(preset):
    return dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn")


def _grad(c):
    tokens = (jnp.arange(SEQ, dtype=jnp.int32) * 7 % c.vocab_size)[None]
    return jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16))


def _output_products(c):
    """The forward-shaped ``wo`` products ([B, H, S, D] x [H, D, E]) of the
    grouped-query kinds in the differentiated step's jaxpr, and that the
    stream's name is among its ``name`` equations."""
    jax.clear_caches()  # a traced block holds the policy it was traced under
    shapes = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    equations = list(jaxpr_walk.equations(jax.make_jaxpr(_grad(c))(shapes).jaxpr))
    assert POST_ATTN in [e.params["name"] for e in equations if e.primitive.name == "name"]
    specs = {(a.heads, a.head_dim) for a in (c.gqa, c.gqa_window) if a is not None}
    return sum(len(jaxpr_walk.products(equations, (1, h, SEQ, d), (h, d, c.hidden)))
               for h, d in specs)


@pytest.mark.parametrize("preset,layers", PRESETS_OF_GROUPED_QUERIES)
def test_remat_attn_runs_a_grouped_query_layers_output_product_once(monkeypatch, preset, layers):
    """A whole step under remat ``attn``, differentiated: ``gqa`` and
    ``gqa_win`` save ``post_attn``, the stream as the mixer's output joins it,
    so ``wo``'s forward-shaped product is in the program ONCE a grouped-query
    layer; with the name taken out of the kinds' tuple the second run holds it
    again. The tuple alone decides."""
    from ray_tpu.models.llama import MIXERS

    c = _config(preset)
    assert sum(k in ("gqa", "gqa_win") for k in c.lead_pattern + c.layer_pattern) == layers
    assert _output_products(c) == layers
    for kind in ("gqa", "gqa_win"):
        monkeypatch.setitem(MIXERS, kind, dataclasses.replace(
            MIXERS[kind], save_names=tuple(n for n in gqa.SAVE_NAMES if n != POST_ATTN)))
    assert _output_products(c) == 2 * layers
    jax.clear_caches()


@pytest.mark.parametrize("preset", [preset for preset, _ in PRESETS_OF_GROUPED_QUERIES])
def test_the_saved_stream_leaves_the_gradients_those_of_no_remat(preset):
    """The step's gradients with the stream saved are those of the step under
    no remat at all, leaf by leaf, to 1e-5 in float32 (a leaf no gradient
    reaches is zero in both)."""
    c = _config(preset)
    params = jax.jit(lambda key: init_params(c, key))(jax.random.PRNGKey(0))
    got = jax.jit(_grad(c))(params)
    want = jax.jit(_grad(dataclasses.replace(c, remat=False)))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (rel(g, w) < 1e-5) if np.asarray(w).any() else not np.asarray(g).any()


def test_both_grouped_query_kinds_save_one_tuple_and_the_stream_is_in_it():
    """One tuple for ``gqa`` and ``gqa_win`` (ISSUE 59: a saved byte of the
    stream spares ``heads x head_dim`` FLOPs of second run, never less than
    the ``hidden`` a byte of the saved q spares), and ``llama.py``'s own
    ``attn`` kind keeps the names it had (``train-4k``'s step has no room for
    24 streams)."""
    from ray_tpu.models.llama import MIXERS

    assert MIXERS["gqa"].save_names == MIXERS["gqa_win"].save_names == gqa.SAVE_NAMES
    assert POST_ATTN in gqa.SAVE_NAMES and len(set(gqa.SAVE_NAMES)) == len(gqa.SAVE_NAMES)
    assert POST_ATTN not in MIXERS["attn"].save_names
