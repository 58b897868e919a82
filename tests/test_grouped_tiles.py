"""The grouped matmuls' tile rule (ops/grouped_matmul.py: ``gmm_tiles``,
``tgmm_tiles``, ``tile_visits``): what it gives at the six routed cells'
shapes, what those tiles cost in rows multiplied under seeded routing, and
the kernels under the RULE's tiles (no override) against a loop over groups,
interpreted on the CPU. No cluster is started here."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import trace_log

# ``ray_tpu.ops`` exports the FUNCTION under the module's name
G = importlib.import_module("ray_tpu.ops.grouped_matmul")
grouped_matmul = G.grouped_matmul

# cell: rows of the call (a held range's ``cap``), groups, hidden, expert
# width, rows that are valid, the busiest group over the mean (ledger, PR 42;
# the last, PR 45)
CELLS = {
    "kimi": (1536, 8, 7168, 2048, 700, 2.63),
    "hybrid": (40960, 64, 2048, 512, 20700, 2.37),
    "window-full": (40960, 32, 3072, 1024, 20500, 1.51),
    "sparse": (8192, 8, 5120, 1536, 4064, 1.70),
    "routed": (131072, 64, 2048, 1024, 131072, 3.36),
    "prerouted": (49152, 16, 2560, 768, 24534, 1.07),
}
# product: kernel, transposed, (K, N) from (hidden, width)
PRODUCTS = {
    "up-fwd": ("gmm", False, lambda h, w: (h, w)),
    "down-fwd": ("gmm", False, lambda h, w: (w, h)),
    "up-dlhs": ("gmm", True, lambda h, w: (w, h)),
    "down-dlhs": ("gmm", True, lambda h, w: (h, w)),
    "up-tgmm": ("tgmm", None, lambda h, w: (h, w)),
    "down-tgmm": ("tgmm", None, lambda h, w: (w, h)),
}
# what the rule gives, after the cut to divisors (PERF.md, PR 43: measured on a
# v5e but the three the table marks; the last two rows, at 2,048 rows a group
# and more, PR 46)
EXPECTED = {
    "kimi": [(128, 7168, 2048), (128, 2048, 7168), (128, 2048, 7168), (128, 7168, 2048),
             (128, 7168, 1024), (128, 2048, 3584)],
    "hybrid": [(128, 2048, 512), (128, 512, 2048), (128, 512, 2048), (128, 2048, 512),
               (128, 2048, 512), (128, 512, 2048)],
    "window-full": [(128, 3072, 1024), (128, 1024, 3072), (128, 1024, 3072),
                    (128, 3072, 1024), (128, 3072, 1024), (128, 1024, 3072)],
    "sparse": [(128, 5120, 1536), (128, 1536, 5120), (128, 1536, 5120), (128, 5120, 1536),
               (128, 5120, 1536), (128, 1536, 5120)],
    "routed": [(256, 2048, 1024), (256, 1024, 2048), (256, 1024, 2048), (256, 2048, 1024),
               (256, 2048, 1024), (256, 1024, 2048)],
    "prerouted": [(256, 2560, 768), (256, 768, 2560), (256, 768, 2560), (256, 2560, 768),
                  (256, 2560, 768), (256, 768, 2560)],
}


def _rule(cell, product):
    m, groups, hidden, width, _, _ = CELLS[cell]
    kernel, transposed, dims = PRODUCTS[product]
    k, n = dims(hidden, width)
    if kernel == "gmm":
        tiles = G.gmm_tiles(m, groups, k, n, 2)
    else:
        tiles = G.tgmm_tiles(m, groups, k, n, 2, 2)
    return kernel, (m, k, n), G._fit_tiles(tiles, m, k, n, jnp.bfloat16)


def _traced(kernel, transposed, m, groups, k, n):
    """What ``kernel_costs()`` holds after the product is traced (not run) at
    these shapes with no override."""
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32)
    if kernel == "gmm":
        rhs = jax.ShapeDtypeStruct((groups, n, k) if transposed else (groups, k, n), jnp.bfloat16)
        jax.eval_shape(lambda l, r, s: G._gmm(l, r, s, None, transpose_rhs=transposed,
                                              tiles=None, interpret=True), lhs, rhs, sizes)
    else:
        dout = jax.ShapeDtypeStruct((m, n), jnp.bfloat16)
        jax.eval_shape(lambda l, d, s: G._tgmm(l, d, s, None, out_dtype=jnp.bfloat16,
                                               tiles=None, interpret=True), lhs, dout, sizes)
    return trace_log.kernel_costs()["moe_" + kernel]


@pytest.mark.parametrize("product", list(PRODUCTS))
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_a_cells_shapes(cell, product):
    kernel, (m, k, n), (tm, tk, tn) = _rule(cell, product)
    groups, transposed = CELLS[cell][1], PRODUCTS[product][1]
    assert (tm, tk, tn) == EXPECTED[cell][list(PRODUCTS).index(product)]
    assert m % tm == 0 and k % tk == 0 and n % tn == 0
    assert tm % 16 == 0 and tk % 128 == 0 and tn % 128 == 0  # bf16 sublanes, lanes
    vmem = (G._gmm_vmem(tm, tk, tn, 2) if kernel == "gmm"
            else G._tgmm_vmem(tm, tk, tn, 2, 2))
    assert vmem <= G._VMEM_BLOCKS
    # one step of the rows a group would hold; the matrix block keeps its index
    # over a group's visits at every row count
    assert tm == (128 if m // groups < 2048 else 256) and tk == k
    cost = _traced(kernel, transposed, m, groups, k, n)
    assert tuple(cost["tiles"]) == (tm, tk, tn) and cost["rhs_resident"] is True
    assert cost["work_items"] == m // tm + groups - 1


def _seeded_sizes(groups, valid, skew, seed):
    """Sizes summing to ``valid`` whose largest over their mean is near ``skew``."""
    z = np.random.default_rng(seed).standard_normal(groups)
    best = None
    for sigma in np.linspace(0.0, 2.5, 126):
        w = np.exp(sigma * z)
        sizes = np.floor(w / w.sum() * valid).astype(np.int64)
        sizes[np.argmax(sizes)] += valid - sizes.sum()
        off = abs(sizes.max() / sizes.mean() - skew)
        if best is None or off < best[0]:
            best = (off, sizes)
    return best[1]


# rows multiplied over rows that exist, at the rule's row tile: the ceiling a
# cell stays under on every seed, and what 512-row tiles cost on seed 43
@pytest.mark.parametrize("cell,ceiling,at_512", [
    ("kimi", 3.0, 6.58), ("hybrid", 1.5, 2.57), ("window-full", 1.3, 1.80),
    ("sparse", 1.4, 1.89), ("routed", 1.15, 1.25), ("prerouted", 1.2, 1.31)])
def test_rows_multiplied_over_rows_that_exist(cell, ceiling, at_512):
    m, groups, _, _, valid, skew = CELLS[cell]
    tm = _rule(cell, "up-fwd")[2][0]
    offset = None if cell == "routed" else jnp.zeros((), jnp.int32)
    for seed in (43, 44, 45):
        sizes = _seeded_sizes(groups, valid, skew, seed)
        visits, rows = G.tile_visits(sizes, m, tm, offset)
        assert rows == visits * tm and valid <= rows < ceiling * valid
        assert visits <= m // tm + groups - 1  # the static bound, ``work_items``
        n_work = G._work_items(jnp.asarray(sizes, jnp.int32), offset, m, tm)[3]
        assert int(n_work[0]) == visits
    sizes = _seeded_sizes(groups, valid, skew, 43)
    assert G.tile_visits(sizes, m, 512, offset)[1] / valid == pytest.approx(at_512, abs=0.01)


def _reference(lhs, rhs, sizes, offset):
    """Every group's rows against its own matrix, rows outside the groups 0."""
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), offset
    for g, size in enumerate(sizes):
        out = out.at[start:start + size].set(lhs[start:start + size] @ rhs[g])
        start += size
    return out


# sizes, (row offset, rows of the call) (None: no offset given, the rows are the
# sizes' sum), K, N -> the rule's (tm, tk, tn) for the forward product; the
# contraction is one block in every one
REGIMES = {
    "many-groups-in-one-tile": ([10, 0, 30, 24, 5, 7, 20, 32], None, 64, 128, (128, 64, 128)),
    "a-group-smaller-than-a-tile": ([100, 0, 300, 112], None, 256, 256, (128, 256, 256)),
    "all-in-the-last-group": ([0, 0, 0, 384], None, 128, 128, (128, 128, 128)),
    "row-offset-given": ([60, 0, 200, 100], (70, 512), 128, 256, (128, 128, 256)),
    "a-wide-contraction-in-one-block": ([31, 97, 0, 128], None, 2304, 256, (128, 2304, 256)),
    # 2,048 rows a group and more: 256-row tiles, an empty group inside the tile
    # that straddles, a contraction of three lane tiles
    "whole-blocks-at-2048-rows-a-group": ([1500, 0, 4644], None, 384, 256, (256, 384, 256)),
    # a held range: the valid rows fill a quarter of the call's, so most work
    # items are past ``n_work``
    "whole-blocks-in-a-held-range": ([700, 0, 833], (70, 6144), 384, 128, (256, 384, 128)),
}


@pytest.mark.parametrize("regime", list(REGIMES))
def test_the_rules_tiles_against_a_loop_over_groups(regime):
    sizes, held, k, n, tiles = REGIMES[regime]
    offset, m = held or (None, sum(sizes))
    keys = jax.random.split(jax.random.PRNGKey(len(sizes) + k), 3)
    lhs = jax.random.normal(keys[0], (m, k)) / 8
    rhs = jax.random.normal(keys[1], (len(sizes), k, n)) / 8
    weight = jax.random.normal(keys[2], (m, n))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    row_offset = None if offset is None else jnp.asarray(offset, jnp.int32)
    before = trace_log.kernel_traces()

    def fn(lhs, rhs):
        return grouped_matmul(lhs, rhs, group_sizes, row_offset=row_offset)

    def ref(lhs, rhs):
        return _reference(lhs, rhs, sizes, offset or 0)

    np.testing.assert_allclose(fn(lhs, rhs), ref(lhs, rhs), atol=2e-4)
    cost = trace_log.kernel_costs()["moe_gmm"]
    assert (tuple(cost["tiles"]), cost["rhs_resident"]) == (tiles, True)
    assert cost["work_items"] == m // tiles[0] + len(sizes) - 1
    got = jax.grad(lambda l, r: (fn(l, r) * weight).sum(), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: (ref(l, r) * weight).sum(), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], atol=2e-3)
    costs = trace_log.kernel_costs()
    # the backward product contracts over N, the transposed one over rows
    assert tuple(costs["moe_gmm"]["tiles"]) == (tiles[0], n, tiles[1])
    assert tuple(costs["moe_tgmm"]["tiles"]) == (tiles[0], tiles[1], n)
    assert costs["moe_gmm"]["rhs_resident"] and costs["moe_tgmm"]["rhs_resident"]
    after = trace_log.kernel_traces()
    for kernel in ("moe_gmm", "moe_tgmm"):  # the kernels ran, not ``ragged_dot``
        assert after.get(f"{kernel}:interpret", 0) > before.get(f"{kernel}:interpret", 0)
        assert after.get(f"{kernel}:ragged_dot", 0) == before.get(f"{kernel}:ragged_dot", 0)
