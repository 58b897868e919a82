"""The held range's adds (``ops/moe_rows.py::sum_rows``, the kernel
``moe_rows``) against plain ``jnp``, interpreted on the CPU; the pair
``models/moe.py`` makes of it and XLA's gather, each the other's gradient;
``_held_range`` through them against ``_all_rows``; and that the
differentiated, rematerialised stacks hold the kernel and no scatter-add of
rows."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jaxpr_walk

from ray_tpu.models import PRESETS, init_params, loss_fn
from ray_tpu.models import moe
from ray_tpu.ops import moe_rows, trace_log
from ray_tpu.ops.moe_rows import sum_rows


def _plain_sum(src, ids, n):
    return jnp.zeros((n, src.shape[1]), jnp.float32).at[jnp.where(ids >= 0, ids, n)].add(
        src.astype(jnp.float32), mode="drop")


def _ids(kind, m, n, rng):
    """Destinations of ``m`` rows among ``n``: ``one`` at most a row a
    destination, ``top_k`` up to four rows on one, ``holes`` a third of the
    rows -1 and the middle tile of destinations named by none, ``none``
    every row -1."""
    if kind == "one":
        return rng.permutation(n)[:m].astype(np.int32)
    if kind == "top_k":
        return np.repeat(rng.permutation(n)[:-(-m // 4)], 4)[:m].astype(np.int32)
    if kind == "none":
        return np.full(m, -1, np.int32)
    tile = moe_rows._tile(n)
    allowed = np.array([i for i in range(n) if i // tile != (n // tile) // 2])
    ids = rng.choice(allowed, size=m).astype(np.int32)
    ids[rng.random(m) < 1 / 3] = -1
    return ids


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["one", "top_k", "holes", "none"])
@pytest.mark.parametrize("n, m, e", [(96, 64, 5120), (48, 80, 7168), (1024, 96, 256)],
                         ids=["40_lane_tiles", "56_lane_tiles", "four_tiles_of_256"])
def test_sum_rows_matches_jnp(n, m, e, kind, dtype):
    rng = np.random.default_rng(n + m)
    m = min(m, n) if kind == "one" else m
    ids = _ids(kind, m, n, rng)
    src = jnp.asarray(rng.standard_normal((m, e)), dtype)
    got = sum_rows(src, jnp.asarray(ids), n)
    want = _plain_sum(src, ids, n)
    assert got.dtype == dtype and got.shape == (n, e)
    # the sum is made in float32 and rounded once
    if kind in ("one", "none"):
        assert (got == want.astype(dtype)).all()
    else:
        tol = 0.04 if dtype == jnp.bfloat16 else 1e-5
        assert float(jnp.abs(got.astype(jnp.float32) - want).max()) <= tol
    assert not np.asarray(got)[np.setdiff1d(np.arange(n), ids)].any()  # named by no row: zeros


def test_the_gather_and_the_add_are_each_others_gradient():
    """``models/moe.py``'s pair on the rows that name a token; a row whose id
    is -1 reads token 0 and gets token 0's cotangent back, which the held
    range never looks at."""
    rng = np.random.default_rng(5)
    n, m, e = 96, 48, 128
    ids = _ids("holes", m, n, rng)
    named = ids >= 0
    ids = jnp.asarray(ids)
    table = jnp.asarray(rng.standard_normal((n, e)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((m, e)), jnp.float32)
    g_rows = jnp.asarray(rng.standard_normal((m, e)), jnp.float32)
    g_table = jnp.asarray(rng.standard_normal((n, e)), jnp.float32)
    taken, pull = jax.vjp(lambda t: moe._take_rows(t, ids), table)
    np.testing.assert_array_equal(taken[named], table[ids[named]])
    np.testing.assert_array_equal(taken[~named], jnp.broadcast_to(table[0], taken[~named].shape))
    np.testing.assert_allclose(pull(g_rows)[0], _plain_sum(g_rows, ids, n), rtol=1e-6, atol=1e-6)
    added, pull = jax.vjp(lambda r: moe._add_rows(r, ids, n), rows)
    np.testing.assert_allclose(added, _plain_sum(rows, ids, n), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(pull(g_table)[0][named], g_table[ids[named]])
    # and the gather's gradient is the kernel, not a scatter-add
    jaxpr = jax.make_jaxpr(jax.grad(lambda t: moe._take_rows(t, ids).sum()))(table)
    names = [e.primitive.name for e in jaxpr_walk.equations(jaxpr.jaxpr)]
    assert "pallas_call" in names and "scatter-add" not in names and "scatter_add" not in names


def test_a_width_off_the_lane_tiling_takes_xla_and_says_so():
    before = trace_log.kernel_traces().get("moe_rows:xla", 0)
    ids = jnp.asarray([3, -1, 0, 3], jnp.int32)
    src = jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64)
    got = sum_rows(src, ids, 8)
    np.testing.assert_array_equal(got, _plain_sum(src, ids, 8))
    assert trace_log.kernel_traces()["moe_rows:xla"] == before + 1


def test_the_cost_entry_is_the_rows_bytes():
    sum_rows(jnp.zeros((32, 256), jnp.bfloat16), jnp.zeros((32,), jnp.int32), 64)
    cost = trace_log.kernel_costs()["moe_rows"]
    assert cost["flops"] == 0 and cost["bytes"] == (32 + 64) * 256 * 2


def _routed(n, k, x, hidden, inter, held, dtype, skew, seed=0):
    """Tokens, the held experts' weights and a routing of ``n`` tokens over
    ``x`` experts; ``skew`` tilts the router towards the held range (under
    ``cap`` still)."""
    key = jax.random.PRNGKey(seed)
    first, count = held
    tokens = jax.random.normal(key, (n, hidden), dtype)
    router = jax.random.normal(jax.random.fold_in(key, 1), (hidden, x), jnp.float32)
    router = router.at[:, first:first + count].multiply(skew)
    weights = {name: (jax.random.normal(jax.random.fold_in(key, i + 2), shape, jnp.float32)
                      * shape[1] ** -0.5).astype(dtype)
               for i, (name, shape) in enumerate((("w_gate", (count, hidden, inter)),
                                                  ("w_up", (count, hidden, inter)),
                                                  ("w_down", (count, inter, hidden))))}
    r = moe.route(tokens, router, top_k=k, norm_topk=True)
    offset = jnp.sum(jnp.where(jnp.arange(x) < first, r["sizes"], 0))
    sizes = jax.lax.dynamic_slice_in_dim(r["sizes"], first, count)
    return tokens, weights, r, sizes, offset


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("skew", [1.0, 2.5], ids=["even", "skewed_under_cap"])
def test_the_held_range_matches_all_rows_in_values_and_gradients(skew, dtype):
    n, k, x, hidden, inter, held = 64, 2, 8, 128, 32, (2, 2)
    tokens, weights, r, sizes, offset = _routed(n, k, x, hidden, inter, held, dtype, skew)
    cap = moe._held_capacity(n * k, held, x)
    total = int(jnp.sum(sizes))
    assert cap == 64 and (total >= 40 if skew > 1 else total <= 32) and total <= cap  # even: 32
    before = trace_log.kernel_traces().get("moe_rows:interpret", 0)

    def compact(t, w, g):
        return moe._held_range(k, cap, t, w, g, r["order"], sizes, offset)

    def whole(t, w, g):
        return moe._all_rows(k, t, w, g, r["order"], r["inv"], sizes, offset)

    cot = jax.random.normal(jax.random.PRNGKey(9), tokens.shape, dtype)

    def value_and_gradients(fn):
        def run(t, w, g):
            out, pull = jax.vjp(fn, t, w, g)
            return out, pull(cot)

        return jax.jit(run)(tokens, weights, r["gates"])

    got, want = value_and_gradients(compact), value_and_gradients(whole)
    assert trace_log.kernel_traces()["moe_rows:interpret"] > before
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("preset", ["latent-sparse-debug", "latent-full-debug"])
def test_the_differentiated_remat_stack_holds_the_kernel_and_no_scatter_add(preset):
    """At a width of one lane tile (and enough experts that the held two get
    the compact path at 48 tokens) the held range's adds are ``moe_rows``
    calls: for each expert layer's place in the program (a scanned period's
    layers share theirs) one forward and two in the backward rule, the add
    of its own forward pass, which nothing reads and the compiler drops, and
    the gather's gradient; none run again under remat, and no scatter-add is
    left under the two scopes."""
    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn", hidden=128,
                            moe_experts=16)
    params = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 48), jnp.int32)
    before = trace_log.kernel_traces().get("moe_rows:interpret", 0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, c, chunk_tokens=16)))(params)
    assert trace_log.kernel_traces()["moe_rows:interpret"] > before
    equations = list(jaxpr_walk.equations(jaxpr.jaxpr))
    kernels = [str(e.params["name"]) for e in equations if e.primitive.name == "pallas_call"]
    assert kernels.count("moe_rows") == 3 * len(c.layer_pattern), kernels.count("moe_rows")
    # what is left of scatter-adds there moves scalars (the gates' gradient), no row
    for e in equations:
        if e.primitive.name in ("scatter-add", "scatter_add"):
            stack = str(e.source_info.name_stack)
            assert e.outvars[0].aval.ndim == 1 or not (
                "moe_dispatch" in stack or "moe_combine" in stack), (stack, e.outvars[0].aval)


def test_all_rows_is_untouched():
    """The routed cell's path: its jaxpr, values and gradients, is the parent
    commit's (the text's SHA-256, taken on the parent)."""
    n, k, x, hidden, inter = 32, 2, 4, 64, 32
    tokens, weights, r, sizes, _ = _routed(n, k, x, hidden, inter, (0, 4), jnp.float32, 1.0)

    def whole(t, w, g):
        return moe._all_rows(k, t, w, g, r["order"], r["inv"], sizes, None)

    cot = jnp.ones_like(tokens)
    text = str(jax.make_jaxpr(lambda t, w, g: jax.vjp(whole, t, w, g)[1](cot))(
        tokens, weights, r["gates"]))
    assert "moe_rows" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == ALL_ROWS_JAXPR_SHA256


ALL_ROWS_JAXPR_SHA256 = "d339b610f6aab408c9f85634617b7a685390cf17476e3916ceaa9285b4023b07"
