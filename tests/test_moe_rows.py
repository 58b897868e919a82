"""The held range's adds and the embedding's gradient
(``ops/moe_rows.py::sum_rows``, the kernel ``moe_rows``) against plain
``jnp``, interpreted on the CPU; the pair ``take_rows`` / ``add_rows`` made
of it and XLA's gather, each the other's gradient; ``_held_range_counted`` through
them against ``_all_rows_counted``; the embedding's lookup through them against
plain indexing; and that the differentiated, rematerialised stacks hold the
kernel and no scatter-add of rows."""

import dataclasses
import hashlib
import math
import re

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
@pytest.mark.parametrize("n, m, e", [(96, 64, 5120), (48, 80, 7168), (1024, 96, 256),
                                     (50, 64, 128)],
                         ids=["40_lane_tiles", "56_lane_tiles", "four_tiles_of_256",
                              "a_tile_under_8_rows_padded"])
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
    """The pair on the rows that name a token; a row whose id
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
    taken, pull = jax.vjp(lambda t: moe_rows.take_rows(t, ids), table)
    np.testing.assert_array_equal(taken[named], table[ids[named]])
    np.testing.assert_array_equal(taken[~named], jnp.broadcast_to(table[0], taken[~named].shape))
    np.testing.assert_allclose(pull(g_rows)[0], _plain_sum(g_rows, ids, n), rtol=1e-6, atol=1e-6)
    added, pull = jax.vjp(lambda r: moe_rows.add_rows(r, ids, n), rows)
    np.testing.assert_allclose(added, _plain_sum(rows, ids, n), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(pull(g_table)[0][named], g_table[ids[named]])
    # and the gather's gradient is the kernel, not a scatter-add
    jaxpr = jax.make_jaxpr(jax.grad(lambda t: moe_rows.take_rows(t, ids).sum()))(table)
    names = [e.primitive.name for e in jaxpr_walk.equations(jaxpr.jaxpr)]
    assert "pallas_call" in names and "scatter-add" not in names and "scatter_add" not in names


@pytest.mark.parametrize("width, smem", [(64, None), (128, 8 * 4)],
                         ids=["width_64", "more_ids_than_scalar_memory"])
def test_a_shape_the_kernel_cannot_take_goes_to_xla_and_says_so(monkeypatch, width, smem):
    """A width off the lane tiling, or more rows than the scalar memory holds
    ids for (the limit lowered to fewer than these 4 rows and 1 tile)."""
    if smem is not None:
        monkeypatch.setattr(moe_rows, "_SMEM_LIMIT", smem)
    before = trace_log.kernel_traces().get("moe_rows:xla", 0)
    ids = jnp.asarray([3, -1, 0, 3], jnp.int32)
    src = jnp.arange(4 * width, dtype=jnp.float32).reshape(4, width)
    got = sum_rows(src, ids, 8)
    np.testing.assert_array_equal(got, _plain_sum(src, ids, 8))
    assert trace_log.kernel_traces()["moe_rows:xla"] == before + 1


def test_the_scalar_memory_limit_admits_every_cells_calls():
    """49,152 held rows into 16,384 tokens (the eighth cell) and 16,384 tokens
    into a vocabulary of 1,187 tiles are far inside it; 131,072 rows, which the
    chip's compiler refuses, are outside."""
    fits = lambda m, n: moe_rows._ids_fit(m, n // moe_rows._tile(n))  # noqa: E731
    assert fits(49152, 16384) and fits(16384, 18992) and fits(97280, 128256)
    assert not fits(131072, 128256)


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
        return moe._held_range_counted(k, cap, t, w, g, r["order"], sizes, offset)[0]

    def whole(t, w, g):
        return moe._all_rows_counted(k, t, w, g, r["order"], r["inv"], sizes, offset)[0]

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
    the gather's gradient; none run again under remat; one more is the
    embedding table's gradient; and no scatter-add of rows is left under the
    two scopes or under ``embed``."""
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
    assert kernels.count("moe_rows") == 3 * len(c.layer_pattern) + 1, kernels.count("moe_rows")
    # what is left of scatter-adds there moves scalars (the gates' gradient), no row
    for e in equations:
        if e.primitive.name in ("scatter-add", "scatter_add"):
            stack = str(e.source_info.name_stack)
            assert e.outvars[0].aval.ndim == 1 or not (
                "moe_dispatch" in stack or "moe_combine" in stack
                or "embed" in stack), (stack, e.outvars[0].aval)


def _lookup(table, tokens):
    """``forward_hidden``'s lookup where no mesh shards the table."""
    b, s = tokens.shape
    return moe_rows.take_rows(table, tokens.reshape(b * s)).reshape(b, s, -1)


# a vocabulary's destination tile: 512 -> 256, 48 -> 16 (as the hybrid cell's
# 18,992 = 16 x 1,187); every batch names the first and the last row of the
# table and repeats ids, ``few`` so often that a row is the sum of a dozen
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab, tile", [(512, 256), (48, 16)], ids=["tile_256", "tile_16"])
@pytest.mark.parametrize("ids", ["spread", "few"])
def test_the_lookups_gradient_is_plain_indexings(ids, vocab, tile, dtype, tol):
    assert moe_rows._tile(vocab) == tile
    rng = np.random.default_rng(vocab)
    tokens = rng.integers(0, vocab if ids == "spread" else 5, size=(2, 32)).astype(np.int32)
    tokens[0, :2], tokens[1, -2:] = (0, vocab - 1), (vocab - 1, 0)
    tokens = jnp.asarray(tokens)
    table = jnp.asarray(rng.standard_normal((vocab, 256)), dtype)
    cot = jnp.asarray(rng.standard_normal((2, 32, 256)), dtype)

    def grad(lookup, table, cot):
        out, pull = jax.vjp(lambda t: lookup(t, tokens), table)
        return out, pull(cot)[0]

    # plain indexing's gradient at float32: off a TPU its bfloat16 scatter-add
    # rounds after every row, where the kernel sums in float32 and rounds once
    got_x, got = grad(_lookup, table, cot)
    want_x, want = grad(lambda t, ids: t[ids], table.astype(jnp.float32), cot.astype(jnp.float32))
    np.testing.assert_array_equal(got_x, want_x.astype(dtype))
    assert got.dtype == dtype and got.shape == table.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=tol, atol=tol)
    unnamed = np.setdiff1d(np.arange(vocab), np.asarray(tokens))
    assert len(unnamed) and not np.asarray(got, np.float32)[unnamed].any()


def test_ids_out_of_range_read_an_end_of_the_table_and_are_added_nowhere():
    """What ``take_rows``'s docstring says of the two cases: -1 reads row 0
    (plain indexing wraps to the last), an id past the table reads the last
    row as plain indexing does, and the gradient gives neither's cotangent
    to any row."""
    table = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    ids = jnp.asarray([3, -1, 8, 11], jnp.int32)
    out, pull = jax.vjp(lambda t: moe_rows.take_rows(t, ids), table)
    np.testing.assert_array_equal(out, table[jnp.asarray([3, 0, 7, 7])])
    np.testing.assert_array_equal(table[ids[2:]], out[2:])
    grad = pull(jnp.ones_like(out))[0]
    np.testing.assert_array_equal(grad, jnp.zeros_like(table).at[3].set(1.0))


def _dense_step(preset, mesh=None):
    c = dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn")
    params = jax.eval_shape(lambda key: init_params(c, key), jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    return jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, c, mesh=mesh, chunk_tokens=16)), params


def _embed_lines(text):
    """The lowered instructions under scope ``embed``, their value names
    left out; a scatter is one line, from its name to the types after its
    body."""
    text = re.sub(r'("stablehlo\.scatter"[^\n]*)\n.*?\n\s*(\}\) [^\n]*)', r"\1 ... \2", text,
                  flags=re.DOTALL)
    # ``rt_pass`` (PR 50) stands beside the scope and is left out, so the
    # lines stay the text the parent of PR 47 lowered
    return [re.sub(r'%[\w#]+|rt_pass = "\w+", ', lambda m: "%" if m.group().startswith("%") else "",
                   line).strip()
            for line in text.splitlines() if 'rt_scope = "embed"' in line]


def _mesh(**axes):
    from ray_tpu.parallel import MeshConfig, create_mesh

    return create_mesh(MeshConfig(**axes), devices=jax.devices()[:math.prod(axes.values())])


@pytest.mark.parametrize("preset, path, mesh", [
    ("debug-128", "interpret", None), ("debug-128", "interpret", {"dp": 1}), ("debug", "xla", None)],
    ids=["width_128", "width_128_on_a_mesh_of_one_device", "width_64"])
def test_a_dense_steps_embedding_gradient_is_the_kernel_at_a_lane_tile_and_xlas_below(
        preset, path, mesh):
    """At a width of 128 the differentiated step holds one ``moe_rows`` call
    and no scatter under ``embed`` (that the call reads under ``embed`` in the
    backward rule: ``tests/test_device_scopes.py``, and compiled for the chip
    ``tests/test_chip_compile_steps.py``), without a mesh and under the mesh
    of one device that the benchmark's one-chip cells run under; at 64
    ``sum_rows`` takes XLA's scatter-add into the float32 table under the
    same scope and says so."""
    before = trace_log.kernel_traces().get(f"moe_rows:{path}", 0)
    step, params = _dense_step(preset, mesh=mesh and _mesh(**mesh))
    equations = jaxpr_walk.equations(jax.make_jaxpr(step)(params).jaxpr)
    assert trace_log.kernel_traces()[f"moe_rows:{path}"] == before + 1
    kernels = [str(e.params["name"]) for e in equations if e.primitive.name == "pallas_call"]
    scatters = [line for line in _embed_lines(jax.jit(step).lower(params).as_text())
                if "stablehlo.scatter" in line]
    if path == "interpret":
        assert kernels.count("moe_rows") == 1 and not scatters
    else:
        v, e = params["embed"].shape
        assert "moe_rows" not in kernels and len(scatters) == 1
        assert scatters[0].endswith(f"-> tensor<{v}x{e}xf32>")


def test_with_a_mesh_of_four_devices_the_lookup_is_the_parents():
    """Under a mesh of more than one device the table may be sharded and
    keeps plain indexing: no ``moe_rows`` in the step, and under ``embed`` the
    text the parent commit lowered (its SHA-256, taken on the parent: a
    gather, and its transpose, a scatter-add into float32 zeros)."""
    def traced():
        return {k: v for k, v in trace_log.kernel_traces().items() if k.startswith("moe_rows")}

    before = traced()
    step, params = _dense_step("debug-128", mesh=_mesh(fsdp=4))
    text = jax.jit(step).lower(params).as_text()
    assert traced() == before and "moe_rows" not in text
    lines = _embed_lines(text)
    assert any("stablehlo.gather" in line for line in lines)
    assert any("stablehlo.scatter" in line for line in lines)
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest() == MESHED_EMBED_SHA256


MESHED_EMBED_SHA256 = "96e2912fba797a5ff1f354792b6a9c615941bafcb904aa45e38638604823a21c"


def test_all_rows_is_untouched():
    """The routed cell's path, differentiated, holds no ``moe_rows`` kernel (its
    values and gradients: ``test_the_held_range_matches_all_rows_in_values_and_gradients``)."""
    n, k, x, hidden, inter = 32, 2, 4, 64, 32
    tokens, weights, r, sizes, _ = _routed(n, k, x, hidden, inter, (0, 4), jnp.float32, 1.0)

    def whole(t, w, g):
        return moe._all_rows_counted(k, t, w, g, r["order"], r["inv"], sizes, None)[0]

    cot = jnp.ones_like(tokens)
    text = str(jax.make_jaxpr(lambda t, w, g: jax.vjp(whole, t, w, g)[1](cot))(
        tokens, weights, r["gates"]))
    assert "moe_rows" not in text
