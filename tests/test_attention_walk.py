"""The flash kernels' tile walk (``ops/attention.py::_tile_walk``): the
table against a brute-force enumeration of the tiles that hold an allowed
(query, key) pair; values and gradients of ``flash_attention`` against
``mha_reference`` at three or more blocks a side; that leaving the dead
tiles out changes no bit; and that every attention kernel of a
differentiated, remat-ed stack takes exactly its table's steps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jaxpr_walk

from ray_tpu.models import PRESETS, init_params, loss_fn
from ray_tpu.models.gqa import window_blocks
from ray_tpu.ops import trace_log
from ray_tpu.ops.attention import _tile_walk, flash_attention, mha_reference

WALKS = {
    # name: (sq, sk, block_q, block_k, causal, window)
    "causal": (4096, 4096, 1024, 1024, True, None),
    "causal_8k": (8192, 8192, 1024, 1024, True, None),
    "non_causal": (384, 512, 128, 128, False, None),
    "window_512": (4096, 4096, 512, 512, True, 512),
    "window_513": (4096, 4096, 512, 512, True, 513),
    "window_wider_than_a_block": (2048, 2048, 256, 256, True, 700),
    "block_q_twice_block_k": (2048, 2048, 512, 256, True, None),
    "block_k_twice_block_q": (2048, 2048, 256, 512, True, None),
    "window_unequal_blocks": (2048, 2048, 512, 256, True, 300),
    "more_keys_than_queries": (1024, 2048, 256, 256, True, None),
    "more_queries_than_keys": (2048, 1024, 256, 256, True, 256),
    "one_block": (512, 512, 512, 512, True, None),
}


def _brute_force(sq, sk, block_q, block_k, causal, window, key_major):
    """[(q block, k block)] in the walk's order and how many are live, from
    every (query, key) pair the in-tile masks allow."""
    q, k = np.arange(sq)[:, None], np.arange(sk)[None, :]
    allowed = np.ones((sq, sk), bool)
    if causal:
        allowed &= q >= k
    if window is not None:
        allowed &= q - k < window
    n_q, n_k = sq // block_q, sk // block_k
    live = allowed.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3))
    tiles = []
    for i in range(n_k if key_major else n_q):
        inner = [j for j in range(n_q if key_major else n_k)
                 if (live[j, i] if key_major else live[i, j])]
        # a row that nothing serves is still written: one step, on its last tile
        inner = inner or [(n_q if key_major else n_k) - 1]
        tiles += [(j, i) if key_major else (i, j) for j in inner]
    return tiles, int(live.sum())


@pytest.mark.parametrize("key_major", [False, True], ids=["query_major", "dkdv_order"])
@pytest.mark.parametrize("case", list(WALKS))
def test_the_walk_is_the_live_tiles_in_the_accumulators_order(case, key_major):
    sq, sk, block_q, block_k, causal, window = WALKS[case]
    (q_blocks, k_blocks, first, last), (grid_steps, live) = _tile_walk(
        sq // block_q, sk // block_k, block_q, block_k, causal, window, key_major=key_major)
    want, want_live = _brute_force(sq, sk, block_q, block_k, causal, window, key_major)
    assert list(zip(q_blocks.tolist(), k_blocks.tolist())) == want
    assert (grid_steps, live) == (len(want), want_live)
    rows = (k_blocks if key_major else q_blocks).tolist()
    assert first.tolist() == [i == 0 or rows[i - 1] != r for i, r in enumerate(rows)]
    assert last.tolist() == [i == len(rows) - 1 or rows[i + 1] != r for i, r in enumerate(rows)]
    assert all(t.dtype == np.int32 for t in (q_blocks, k_blocks, first, last))
    if case in ("causal", "causal_8k"):  # the triangle: 10 of 16 tiles, 36 of 64
        n = sq // block_q
        assert len(want) == live == n * (n + 1) // 2
    if case == "non_causal":  # the whole rectangle, the steps it took before
        assert len(want) == live == 12


def _operands(key, b, hq, hkv, sq, sk, d, dv):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (b, hq, sq, d)), jax.random.normal(ks[1], (b, hkv, sk, d)),
            jax.random.normal(ks[2], (b, hkv, sk, dv)), jax.random.normal(ks[3], (b, hq, sq, dv)))


def _key_sets(key, b, s, share=0.4):
    keep = jax.random.uniform(key, (b, s, s)) < share
    return ((keep | jnp.eye(s, dtype=bool)) & jnp.tril(jnp.ones((s, s), bool))).astype(jnp.int8)


VALUES = {
    # name: (b, hq, hkv, s, d, dv, flash_attention's keywords, under key sets)
    "mha_3_blocks": (2, 2, 2, 96, 16, 16, dict(block_q=32, block_k=32), False),
    "grouped_6_to_1": (1, 6, 1, 128, 16, 16, dict(block_q=32, block_k=32), False),
    "grouped_9_to_1": (1, 9, 1, 96, 16, 16, dict(block_q=32, block_k=32), False),
    "narrow_value_head_key_sets_lse": (2, 2, 2, 96, 24, 16,
                                       dict(block_q=32, block_k=32, top_k=40, return_lse=True),
                                       True),
    "window_at_512_blocks": (1, 2, 1, 2048, 16, 16,
                             dict(block_q=512, block_k=512, window=512), False),
    "non_causal": (1, 2, 2, 96, 16, 16, dict(block_q=32, block_k=32, causal=False), False),
    "unequal_blocks": (1, 2, 2, 128, 16, 16, dict(block_q=64, block_k=32), False),
}


@pytest.mark.parametrize("case", list(VALUES))
def test_values_and_gradients_match_mha_reference(case):
    b, hq, hkv, s, d, dv, kw, masked = VALUES[case]
    key = jax.random.PRNGKey(40)
    q, k, v, w = _operands(key, b, hq, hkv, s, s, d, dv)
    kw = dict(kw)
    with_lse = kw.pop("return_lse", False)
    top_k = kw.pop("top_k", None)
    if masked:
        kw["mask"] = _key_sets(jax.random.fold_in(key, 9), b, s)

    def got(*x):
        out = flash_attention(*x, top_k=top_k, return_lse=with_lse, **kw)
        return out[0] if with_lse else out

    plain = {n: kw[n] for n in ("causal", "window", "mask") if n in kw}
    want = lambda *x: mha_reference(*x, **plain)  # noqa: E731
    np.testing.assert_allclose(got(q, k, v), want(q, k, v), atol=2e-5, rtol=2e-5)
    if with_lse:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        lse = jax.nn.logsumexp(jnp.where(kw["mask"][:, None] != 0, logits, -jnp.inf), axis=-1)
        np.testing.assert_allclose(
            flash_attention(q, k, v, top_k=top_k, return_lse=True, **kw)[1], lse,
            atol=2e-5, rtol=2e-5)
    grads = lambda f: jax.grad(lambda *x: jnp.sum(f(*x) * w), (0, 1, 2))(q, k, v)  # noqa: E731
    for a, b_ in zip(grads(got), grads(want)):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (6, 1)], ids=["mha", "grouped"])
def test_leaving_the_dead_tiles_out_changes_no_bit(hq, hkv):
    """The causal walk against the WHOLE rectangle walked under key sets that
    are the causal triangle (the parent's steps, a tile above the diagonal
    computed all masked instead of skipped): a dead tile adds exp(-1e30 - m)
    = 0 to every accumulator, so outputs and gradients are the same bits."""
    s = 128
    q, k, v, w = _operands(jax.random.PRNGKey(41), 2, hq, hkv, s, s, 16, 16)
    triangle = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), jnp.int8)), (2, s, s))
    walks = {
        "triangle": lambda *x: flash_attention(*x, block_q=32, block_k=32),
        "rectangle": lambda *x: flash_attention(*x, causal=False, mask=triangle,
                                                block_q=32, block_k=32),
    }
    out = {n: (f(q, k, v),) + jax.grad(lambda *x, f=f: jnp.sum(f(*x) * w), (0, 1, 2))(q, k, v)
           for n, f in walks.items()}
    costs = trace_log.kernel_costs()
    assert costs["flash_bwd_dkdv"]["grid_steps"] == costs["flash_bwd_dkdv"]["live_steps"] == 10
    assert costs["attn_sel_bwd_dkdv"]["grid_steps"] == 16
    for a, b_ in zip(out["triangle"], out["rectangle"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("preset,families", [
    ("debug", {"flash"}),
    ("latent-sparse-debug", {"attn_sel", "attn_win"}),
    ("window-moe-debug", {"flash", "attn_win"}),
])
def test_every_attention_kernel_of_a_remat_stack_takes_its_tables_steps(preset, families):
    """Traced (nothing runs) at 1 x 4,096 tokens, four of the models'
    1,024-blocks a side: every ``flash_*`` / ``attn_sel_*`` / ``attn_win_*``
    call of the differentiated stack has a grid whose last axis is its table's
    length, ten for the square causal calls where the rectangle was sixteen,
    and the kernels' own record says every step computes."""
    config = dataclasses.replace(PRESETS[preset], remat_policy="attn")
    params = jax.eval_shape(lambda key: init_params(config, key), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, t: loss_fn(p, {"tokens": t}, config, chunk_tokens=1024)))(params, tokens)
    calls = [e for e in jaxpr_walk.equations(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
             and str(e.params["name"]).startswith(("flash_", "attn_sel_", "attn_win_"))]
    assert {str(e.params["name"]).rsplit("_", 2 if "bwd" in str(e.params["name"]) else 1)[0]
            for e in calls} == families
    assert len(calls) >= 3 * len(families)
    for eqn in calls:
        name, grid = str(eqn.params["name"]), eqn.params["grid_mapping"].grid
        tables = [v.aval for v in eqn.invars[:4]]
        assert all(t.shape == (grid[-1],) and t.dtype == jnp.int32 for t in tables), name
        if not name.startswith("attn_win_"):
            assert grid[-1] == 4 * 5 // 2, (name, grid)
        elif preset == "latent-sparse-debug":
            # one head a group, 512-blocks: the diagonal's tile and the one behind it
            assert grid[-1] == 8 + 7, (name, grid)
        else:
            # three query heads a kv head, folded under a window of 5: the band of a
            # query block is one tile, and dK/dV's key block meets the query blocks
            # that hold its own keys and the next block's first four queries
            block_q, block_k = window_blocks(config.gqa_window.window, 3)
            per_key_block = block_k // block_q + 1
            want = (4096 // block_k * per_key_block - 1 if name.endswith("dkdv")
                    else 4096 // block_q)
            assert grid[-1] == want, (name, grid)
            assert eqn.params["grid_mapping"].grid[1] == config.gqa_window.kv_heads
        record = trace_log.kernel_costs()[name]
        assert record["grid_steps"] == record["live_steps"] == grid[-1], (name, record)


@pytest.mark.parametrize("hq,hkv,window,want", [
    (8, 8, 512, {"fwd": (1, [512, 512], 0.50), "bwd_dq": (1, [512, 512], 0.50),
                 "bwd_dkdv": (1, [512, 512], 0.50)}),
    (72, 8, 512, {"fwd": (9, [1152, 640], 0.7875), "bwd_dq": (9, [1152, 640], 0.7875),
                  "bwd_dkdv": (9, [1152, 256], 0.6667)}),
    (28, 4, 4096, {"fwd": (7, [1792, 512], 0.8889), "bwd_dq": (7, [1792, 512], 0.8889),
                   "bwd_dkdv": (7, [1792, 512], 0.8889)}),
], ids=["one_head_a_group_512", "laguna_72to8_512", "smallthinker_28to4_4096"])
def test_the_window_kernels_record_their_tiles_and_the_pairs_they_walk(hq, hkv, window, want):
    """Traced (nothing runs) at 1 x 16,384 x 128 with the blocks ``models/gqa.py``
    gives the call: ``kernel_costs()`` holds the query heads a tile takes, a
    step's [rows, keys] and the pairs a head's steps walk; kept over walked is
    0.50 for one head a tile at 512 keys (two 512-blocks a query block) and the
    folded rule's at 72 : 8 (a band of 640 keys for the 512-639 a query block
    needs; dK/dV, whose key block of 256 meets six query blocks, walks 1.5 x)."""
    s, d = 16384, 128
    q = jax.ShapeDtypeStruct((1, hq, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, hkv, s, d), jnp.bfloat16)
    block_q, block_k = window_blocks(window, hq // hkv)
    jax.make_jaxpr(jax.grad(lambda *x: flash_attention(
        *x, window=window, block_q=block_q, block_k=block_k).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, kv, kv)
    kept = window * s - window * (window - 1) / 2
    for part, (heads, tiles, share) in want.items():
        record = trace_log.kernel_costs()[f"attn_win_{part}"]
        assert record["flops"] == 2.0 * hq * kept * d * {"fwd": 2, "bwd_dq": 3, "bwd_dkdv": 4}[part]
        assert (record["heads_a_tile"], record["tiles"]) == (heads, tiles), (part, record)
        assert record["walked_pairs"] == record["grid_steps"] * tiles[0] // heads * tiles[1]
        assert kept / record["walked_pairs"] == pytest.approx(share, abs=6e-4), (part, record)
        assert record["grid_steps"] == record["live_steps"]
