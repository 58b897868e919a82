"""Which blocks of keys a query attends, chosen with no parameters of its
own from the attention's own q and k (InfLLM-v2: the MiniCPM4 report, arXiv
2506.07900, section 2.2), one choice a kv group.

For a kv head's keys ``k`` and the queries of the G heads it serves
(``kernel_size`` 32, ``kernel_stride`` 16, ``block_size`` 64, ``init_blocks``
1, ``window_size`` 2048, ``topk`` 64 as published):

    pooled    Kp_i     = mean(k[16 i : 16 i + 32])
    head      p_j[t,.] = softmax_i(scale q_j[t] . Kp_i)   over the i whose
                                                          window ends at or before t
    group     P[t, i]  = sum_j p_j[t, i]
    block     s[t, b]  = max of P[t, i] over the i whose window meets block b
                         (i = 4 b - 1 .. 4 b + 3)
    set       B[t]     = block 0, the blocks that hold keys t - 2047 .. t, and
                         the best-scoring other blocks that t can see, 64 in all
                         (every block t can see while those are fewer)

``select_blocks`` returns the sets as [B, KH, T, T / 64] int8: 8 MB a layer at
16k where a [T, T] mask is 268. No gradient passes (its inputs are cut from
the graph). The 64th place is found as ``select_top_k`` finds it, bit by bit
with no sort; of the blocks that tie there the earliest go in, so a set is 64
blocks and no more (as a stable sort by falling score would choose).

The head scores of a whole row are [B, H, T, T / 16] float32, 2 GB at 32 heads
and 16k: the selection runs a chunk of queries at a time (``lax.map``), whose
[B, H, 1024, T / 16] XLA fuses into the group sum. It is plain XLA: no kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .sparse_index import _ordered_bits, kth_largest

_NEG = -1e30
_FORCED = 3e38      # above every score, finite: a forced block's place in the order
QUERY_CHUNK = 1024


def pooled_keys(k, *, kernel_size: int, kernel_stride: int, count: int):
    """k [B, KH, T, D] -> the first ``count`` pooled keys [B, KH, count, D]
    float32; a window that runs past T is averaged over zeros (no query sees
    it: ``pooled_visible``). Windows are whole strides."""
    b, kh, t, d = k.shape
    per = kernel_size // kernel_stride
    if kernel_size % kernel_stride:
        raise ValueError("a pooling window is a whole number of strides")
    rows = (count + per - 1) * kernel_stride
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, max(rows - t, 0)), (0, 0)))[:, :, :rows]
    strides = k.reshape(b, kh, count + per - 1, kernel_stride, d).mean(axis=3)
    return sum(strides[:, :, r:r + count] for r in range(per)) / per


def pooled_visible(t, count: int, *, kernel_size: int, kernel_stride: int):
    """[len(t), count] bool: pooled key i's window ends at or before query t."""
    ends = jnp.arange(count) * kernel_stride + kernel_size - 1
    return ends[None, :] <= t[:, None]


def block_scores(group, *, kernel_size: int, kernel_stride: int, block_size: int):
    """P [..., per * NB] (>= 0) -> s [..., NB]: the max over the pooled keys
    whose window meets the block."""
    per = block_size // kernel_stride
    back = (kernel_size - 1) // kernel_stride      # windows that start before the block
    if block_size % kernel_stride or back > per:
        raise ValueError("a block is a whole number of strides, and no shorter than a window")
    grouped = group.reshape(group.shape[:-1] + (-1, per))
    best = grouped.max(axis=-1)
    for r in range(1, back + 1):
        before = grouped[..., :-1, per - r]
        best = jnp.maximum(best, jnp.pad(before, [(0, 0)] * (before.ndim - 1) + [(1, 0)]))
    return best


def forced_blocks(t, n_blocks: int, *, block_size: int, init_blocks: int, window_size: int):
    """[len(t), NB] bool: the blocks every set holds: the first, and those
    that hold a key of the window (a query's own block among them)."""
    b = jnp.arange(n_blocks)[None, :]
    first = jnp.maximum(t - (window_size - 1), 0) // block_size
    return ((b < init_blocks) | (b >= first[:, None])) & (b <= (t // block_size)[:, None])


def _chunk_sets(q, pooled, t, *, sm_scale, kernel_size, kernel_stride, block_size,
                init_blocks, window_size, topk):
    """The sets of one chunk of queries: q [B, KH, G, Q, D], pooled [B, KH, P,
    D], t [Q] -> [B, KH, Q, NB] int8."""
    count = pooled.shape[2]
    n_blocks = count * kernel_stride // block_size
    s = jnp.einsum("bkgqd,bkpd->bkgqp", q, pooled.astype(q.dtype),
                   preferred_element_type=jnp.float32) * sm_scale
    seen = pooled_visible(t, count, kernel_size=kernel_size, kernel_stride=kernel_stride)
    s = jnp.where(seen, s, _NEG)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True)) * seen
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    score = block_scores(p.sum(axis=2), kernel_size=kernel_size, kernel_stride=kernel_stride,
                         block_size=block_size)
    forced = forced_blocks(t, n_blocks, block_size=block_size, init_blocks=init_blocks,
                           window_size=window_size)
    visible = jnp.arange(n_blocks)[None, :] <= (t // block_size)[:, None]
    # 0 is below every visible block's pattern: what a query cannot see never counts
    keys = jnp.where(visible, _ordered_bits(jnp.where(forced, _FORCED, score)), jnp.uint32(0))
    # a pooled window meets two blocks, so neighbours tie exactly whenever it is
    # the best of both: at the last place the earlier blocks of a tie go in
    kth = kth_largest(keys, topk)[..., None]
    above, ties = keys > kth, visible & (keys == kth)
    room = topk - above.sum(axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))).astype(jnp.int8)


def select_blocks(q, k, *, sm_scale: float, kernel_size: int = 32, kernel_stride: int = 16,
                  block_size: int = 64, init_blocks: int = 1, window_size: int = 2048,
                  topk: int = 64):
    """q [B, H, T, D], k [B, KH, T, D], T a multiple of ``block_size`` -> the
    block sets [B, KH, T, T / block_size] int8, constants of the graph."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    b, h, t, d = q.shape
    kh = k.shape[1]
    if t % block_size:
        raise ValueError(f"{t} positions are no whole number of blocks of {block_size}")
    pooled = pooled_keys(k, kernel_size=kernel_size, kernel_stride=kernel_stride,
                         count=t // kernel_stride)
    chunk = max(c for c in range(block_size, min(QUERY_CHUNK, t) + 1, block_size) if t % c == 0)
    n = t // chunk
    # [n, B, KH, G, chunk, D]: a chunk of queries of every head a step
    chunks = jnp.moveaxis(q.reshape(b, kh, h // kh, n, chunk, d), 3, 0)
    starts = jnp.arange(n, dtype=jnp.int32) * chunk

    def one(xs):
        q_c, t0 = xs
        return _chunk_sets(q_c, pooled, t0 + jnp.arange(chunk, dtype=jnp.int32),
                           sm_scale=sm_scale, kernel_size=kernel_size,
                           kernel_stride=kernel_stride, block_size=block_size,
                           init_blocks=init_blocks, window_size=window_size, topk=topk)

    sets = jax.lax.map(one, (chunks, starts))              # [n, B, KH, chunk, NB]
    return jnp.moveaxis(sets, 0, 2).reshape(b, kh, t, t // block_size)


def set_counters(sets, *, block_size: int, init_blocks: int, window_size: int) -> dict:
    """What a layer's sets [B, KH, T, NB] count: ``kept_share``, the (query,
    key) pairs attended over the causal pairs (a query's own block is always
    in its set, and it sees that block's keys up to itself), and
    ``forced_share``, the share of a set's blocks that are the first or the
    window's."""
    t, n_blocks = sets.shape[2:]
    pos = jnp.arange(t)
    blocks = sets.astype(jnp.float32).sum(axis=-1)                       # [B, KH, T]
    kept = block_size * blocks - (block_size - 1 - pos % block_size)
    forced = forced_blocks(pos, n_blocks, block_size=block_size, init_blocks=init_blocks,
                           window_size=window_size)
    in_set = (sets != 0) & forced
    return {"kept_share": kept.mean(axis=(0, 1)).sum() / (t * (t + 1) / 2),
            "forced_share": in_set.sum() / jnp.maximum(blocks.sum(), 1.0)}
