"""The block selection against a sort, and attention under block sets
(Pallas interpreted on the CPU) against plain masked attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import trace_log
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.block_select import (block_scores, forced_blocks, pooled_keys, select_blocks,
                                      set_counters)

SIZES = dict(kernel_size=8, kernel_stride=4, block_size=16, init_blocks=1, window_size=40,
             topk=8)


def _qkv(seed, t, b=1, h=4, kh=2, d=32, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, t, d), dtype),
            jax.random.normal(ks[1], (b, kh, t, d), dtype),
            jax.random.normal(ks[2], (b, kh, t, d), dtype))


def _sets_by_sort(q, k, *, sm_scale, kernel_size, kernel_stride, block_size, init_blocks,
                  window_size, topk):
    """The selection written out with numpy loops and a stable sort."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, h, t, d = q.shape
    kh, g, nb = k.shape[1], h // k.shape[1], t // block_size
    sets = np.zeros((b, kh, t, nb), np.int8)
    for bi in range(b):
        for ki in range(kh):
            n_pool = (t - kernel_size) // kernel_stride + 1
            pooled = np.stack([k[bi, ki, i * kernel_stride:i * kernel_stride + kernel_size]
                               .mean(0) for i in range(n_pool)])
            # bfloat16 as the program's product takes them
            pooled = np.asarray(jnp.asarray(pooled, jnp.bfloat16), np.float64)
            for ti in range(t):
                seen = [i for i in range(n_pool) if i * kernel_stride + kernel_size - 1 <= ti]
                group = np.zeros(n_pool)
                for j in range(g):
                    if seen:
                        s = pooled[seen] @ q[bi, ki * g + j, ti] * sm_scale
                        p = np.exp(s - s.max())
                        group[seen] += p / p.sum()
                score = np.full(nb, -1.0)
                for blk in range(ti // block_size + 1):
                    meets = [i for i in range(n_pool)
                             if i * kernel_stride + kernel_size - 1 >= blk * block_size
                             and i * kernel_stride <= blk * block_size + block_size - 1]
                    score[blk] = max([group[i] for i in meets], default=0.0)
                    if blk < init_blocks or blk * block_size + block_size - 1 >= ti - (
                            window_size - 1):
                        score[blk] = np.inf
                order = np.argsort(-score, kind="stable")[:topk]
                sets[bi, ki, ti, [o for o in order if o <= ti // block_size]] = 1
    return sets


@pytest.mark.parametrize("t", [64, 208, 512])
def test_the_sets_are_the_sorts(t):
    q, k, _ = _qkv(0, t)
    got = np.asarray(select_blocks(q, k, sm_scale=32 ** -0.5, **SIZES))
    want = _sets_by_sort(q, k, sm_scale=32 ** -0.5, **SIZES)
    assert got.shape == want.shape == (1, 2, t, t // 16)
    # a rounding of the float32 scores may flip a block at the last place
    agree = (got == want).all(axis=-1).mean()
    assert agree > 0.97
    assert (got.sum(-1) == np.minimum(np.arange(t) // 16 + 1, 8)).all()


def test_the_pieces_pooled_keys_block_scores_and_forced_blocks():
    _, k, _ = _qkv(1, 64)
    pooled = pooled_keys(k, kernel_size=8, kernel_stride=4, count=16)
    want = np.asarray(k, np.float32)[0, 1, 20:28].mean(0)
    np.testing.assert_allclose(pooled[0, 1, 5], want, rtol=1e-6, atol=1e-6)
    # pooled key 3 (keys 12..19) meets blocks 0 and 1; pooled key 4 only block 1
    group = jnp.zeros((16,)).at[3].set(0.5).at[4].set(0.25).at[15].set(0.75)
    got = block_scores(group, kernel_size=8, kernel_stride=4, block_size=16)
    np.testing.assert_array_equal(got, [0.5, 0.5, 0.0, 0.75])
    forced = np.asarray(forced_blocks(jnp.asarray([0, 47, 100]), 8, block_size=16, init_blocks=1,
                                      window_size=40))
    assert forced[0].tolist() == [1, 0, 0, 0, 0, 0, 0, 0]
    assert forced[1].tolist() == [1, 1, 1, 0, 0, 0, 0, 0]       # keys 8..47
    assert forced[2].tolist() == [1, 0, 0, 1, 1, 1, 1, 0]       # keys 61..100


def test_the_counters_count_kept_pairs_and_forced_blocks():
    q, k, _ = _qkv(2, 256)
    sets = select_blocks(q, k, sm_scale=32 ** -0.5, **SIZES)
    got = set_counters(sets, block_size=16, init_blocks=1, window_size=40)
    keys = np.repeat(np.asarray(sets), 16, axis=-1) * np.tri(256, dtype=np.int8)
    assert abs(float(got["kept_share"]) - keys.sum() / 2 / (256 * 257 / 2)) < 1e-6
    assert 0.3 < float(got["forced_share"]) < 0.9
    dense = jnp.broadcast_to(np.arange(4)[None, :] <= np.arange(64)[:, None] // 16,
                             (1, 2, 64, 4)).astype(jnp.int8)
    assert abs(float(set_counters(dense, block_size=16, init_blocks=1, window_size=40)[
        "kept_share"]) - 1.0) < 1e-6


@pytest.mark.parametrize("t,blocks", [(512, 128), (208, 128), (256, 64)])
def test_attention_under_block_sets_equals_the_masked_reference_with_gradients(t, blocks):
    q, k, v = _qkv(3, t)
    sets = select_blocks(q, k, sm_scale=32 ** -0.5, **SIZES)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_sets=sets, set_block=16, block_q=blocks, block_k=blocks)
    plain = lambda q, k, v: mha_reference(q, k, v, block_sets=sets, set_block=16)  # noqa: E731
    assert float(jnp.abs(f32(kernel(q, k, v)) - f32(plain(q, k, v))).max()) < 2e-2
    loss = lambda fn: lambda q, k, v: jnp.sum(f32(fn(q, k, v)) ** 2)  # noqa: E731
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.linalg.norm(f32(a) - f32(b)) / jnp.linalg.norm(f32(b))) < 1e-2
    costs = trace_log.kernel_costs()
    assert {"attn_blk_fwd", "attn_blk_bwd_dq", "attn_blk_bwd_dkdv"} <= set(costs)
    assert costs["attn_blk_fwd"]["live_steps"] == costs["attn_blk_fwd"]["grid_steps"]


def test_a_row_shorter_than_top_k_blocks_is_dense_causal_attention():
    q, k, v = _qkv(4, 128)                      # 8 blocks, top-8: every set is whole
    sets = select_blocks(q, k, sm_scale=32 ** -0.5, **SIZES)
    got = flash_attention(q, k, v, block_sets=sets, set_block=16, block_q=64, block_k=64)
    want = flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_under_a_window_of_one_key_only_the_first_and_the_own_block_are_forced():
    q, k, _ = _qkv(5, 512)
    with_local = select_blocks(q, k, sm_scale=32 ** -0.5, **SIZES)
    without = select_blocks(q, k, sm_scale=32 ** -0.5, **{**SIZES, "window_size": 1})
    assert float((with_local != without).any(axis=-1).mean()) > 0.3
    own = np.asarray(without)[0, :, np.arange(512), np.arange(512) // 16]
    assert own.all() and np.asarray(without)[..., 0].all()
