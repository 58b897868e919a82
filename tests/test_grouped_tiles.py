"""The grouped matmuls' tile rule (ops/grouped_matmul.py: ``gmm_tiles``,
``tgmm_tiles``, ``tile_visits``): what it gives at the five routed cells'
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
# width, rows that are valid, the busiest group over the mean (ledger, PR 42)
CELLS = {
    "kimi": (1536, 8, 7168, 2048, 700, 2.63),
    "hybrid": (40960, 64, 2048, 512, 20700, 2.37),
    "window-full": (40960, 32, 3072, 1024, 20500, 1.51),
    "sparse": (8192, 8, 5120, 1536, 4064, 1.70),
    "routed": (131072, 64, 2048, 1024, 131072, 3.36),
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
# v5e but the three the table marks); the routed cell's are PR 26's
EXPECTED = {
    "kimi": [(128, 7168, 2048), (128, 2048, 7168), (128, 2048, 7168), (128, 7168, 2048),
             (128, 7168, 1024), (128, 2048, 3584)],
    "hybrid": [(128, 2048, 512), (128, 512, 2048), (128, 512, 2048), (128, 2048, 512),
               (128, 2048, 512), (128, 512, 2048)],
    "window-full": [(128, 3072, 1024), (128, 1024, 3072), (128, 1024, 3072),
                    (128, 3072, 1024), (128, 3072, 1024), (128, 1024, 3072)],
    "sparse": [(128, 5120, 1536), (128, 1536, 5120), (128, 1536, 5120), (128, 5120, 1536),
               (128, 5120, 1536), (128, 1536, 5120)],
    "routed": [(512, 2048, 1024), (512, 1024, 1024), (512, 1024, 2048), (512, 1024, 1024),
               (512, 2048, 1024), (512, 1024, 1024)],
}


def _rule(cell, product):
    m, groups, hidden, width, _, _ = CELLS[cell]
    kernel, transposed, dims = PRODUCTS[product]
    k, n = dims(hidden, width)
    if kernel == "gmm":
        tiles = G.gmm_tiles(m, groups, k, n, 2, transposed)
    else:
        tiles = G.tgmm_tiles(m, groups, k, n, 2, 2)
    return kernel, (m, k, n), G._fit_tiles(tiles, m, k, n, jnp.bfloat16)


@pytest.mark.parametrize("product", list(PRODUCTS))
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_a_cells_shapes(cell, product):
    kernel, (m, k, n), (tm, tk, tn) = _rule(cell, product)
    assert (tm, tk, tn) == EXPECTED[cell][list(PRODUCTS).index(product)]
    assert m % tm == 0 and k % tk == 0 and n % tn == 0
    assert tm % 16 == 0 and tk % 128 == 0 and tn % 128 == 0  # bf16 sublanes, lanes
    vmem = (G._gmm_vmem(tm, tk, tn, 2) if kernel == "gmm"
            else G._tgmm_vmem(tm, tk, tn, 2, 2))
    assert vmem <= G._VMEM_LIMIT
    if cell == "routed":  # the parent's constants, cut as the parent cut them
        asked = (512, 1024, 2048) if PRODUCTS[product][1] else (512, 2048, 1024)
        assert (tm, tk, tn) == G._fit_tiles(asked, m, k, n, jnp.bfloat16)
    else:  # the matrix block keeps its index over a group's visits
        assert tm == 128 and tk == k


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
    ("sparse", 1.4, 1.89), ("routed", 1.3, 1.25)])
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


# sizes, row offset (None: not given), K, N -> the rule's (tm, tk, tn) for the
# forward product and whether the contraction is one block there
REGIMES = {
    "many-groups-in-one-tile": ([10, 0, 30, 24, 5, 7, 20, 32], None, 64, 128,
                                (128, 64, 128), True),
    "a-group-smaller-than-a-tile": ([100, 0, 300, 112], None, 256, 256,
                                    (128, 256, 256), True),
    "all-in-the-last-group": ([0, 0, 0, 384], None, 128, 128, (128, 128, 128), True),
    "row-offset-given": ([60, 0, 200, 100], 70, 128, 256, (128, 128, 256), True),
    "a-wide-contraction-in-one-block": ([31, 97, 0, 128], None, 2304, 256,
                                        (128, 2304, 256), True),
    "the-ceiling-splits-the-contraction": ([1500, 2596], None, 2304, 128,
                                           (512, 1152, 128), False),
}


@pytest.mark.parametrize("regime", list(REGIMES))
def test_the_rules_tiles_against_a_loop_over_groups(regime):
    sizes, offset, k, n, tiles, resident = REGIMES[regime]
    m = 512 if offset is not None else sum(sizes)
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
    assert (tuple(cost["tiles"]), cost["rhs_resident"]) == (tiles, resident)
    assert cost["work_items"] == m // tiles[0] + len(sizes) - 1
    got = jax.grad(lambda l, r: (fn(l, r) * weight).sum(), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: (ref(l, r) * weight).sum(), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], atol=2e-3)
    costs = trace_log.kernel_costs()
    # the backward product contracts over N, the transposed one over rows
    assert tuple(costs["moe_gmm"]["tiles"]) == (tiles[0], n, tiles[1])
    assert tuple(costs["moe_tgmm"]["tiles"]) == (tiles[0], tiles[1], n)
    assert costs["moe_tgmm"]["rhs_resident"] == resident
    after = trace_log.kernel_traces()
    for kernel in ("moe_gmm", "moe_tgmm"):  # the kernels ran, not ``ragged_dot``
        assert after.get(f"{kernel}:interpret", 0) > before.get(f"{kernel}:interpret", 0)
        assert after.get(f"{kernel}:ragged_dot", 0) == before.get(f"{kernel}:ragged_dot", 0)
