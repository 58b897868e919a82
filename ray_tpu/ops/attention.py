"""Flash attention as a Pallas TPU kernel.

Online-softmax tiled attention (Dao et al.) laid out for the MXU: the grid
iterates (batch, head, tile; under a window and grouped queries the head is
a KV head and a tile holds its whole group), and TPU grids execute the
trailing axis sequentially on-core, so f32 accumulators live in VMEM scratch
across a row's tiles. The tile axis walks a TABLE (``_tile_walk``, scalar-prefetched
into SMEM) of the (q block, k block) tiles that hold a pair a query may
see: the causal triangle, a window's band, or the whole rectangle, query
block by query block with the key blocks inner (key-major for dK/dV). A
tile above the diagonal is no grid step, so none is taken and none fetches
K, V or key sets. Inputs stay bf16 for the MXU; softmax statistics and the
output accumulator are f32.

The reference has no attention kernel of its own (it delegates all model
compute to torch/vLLM); this is the TPU-native equivalent of the kernels
those stacks supply.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .trace_log import (note_attention_cost, note_block_set_cost, note_flash_cost,
                        note_kernel_trace)

NEG_INF = -1e30


def mha_reference(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                  window: int | None = None, mask=None, block_sets=None, set_block: int = 64):
    """Pure-jnp attention; ground truth for kernel tests and the CPU path.

    Shapes: q [B, Hq, S, D], k [B, Hkv, S, D], v [B, Hkv, S, Dv]; GQA when
    Hq > Hkv. ``window``: query t sees keys t - window + 1 .. t; ``mask``
    [B, Sq, Sk] (non-zero: allowed), one key set a query row for all heads;
    ``block_sets`` [B, Hkv, Sq, Sk / set_block]: one set a query row and kv
    head, by blocks of ``set_block`` keys.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    key_mask = mask
    if causal:
        sk = k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    if window is not None:
        t = jnp.arange(sq)[:, None] + (k.shape[2] - sq) - jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(t < window, logits, NEG_INF)
    if key_mask is not None:
        logits = jnp.where(key_mask[:, None] != 0, logits, NEG_INF)
    if block_sets is not None:
        keys = jnp.repeat(block_sets, set_block, axis=-1)[..., :k.shape[2]]
        logits = jnp.where(jnp.repeat(keys, hq // hkv, axis=1) != 0, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(probs.dtype)).astype(q.dtype)


def _tile_walk(n_q: int, n_k: int, block_q: int, block_k: int, causal: bool,
               window: int | None, key_major: bool = False):
    """The tiles a kernel's last grid axis walks: int32 tables (q block, k
    block, first of its row, last of its row), a row being the accumulator's
    block: a query block with its key blocks ascending, or for dK/dV
    (``key_major``) a key block with its query blocks ascending. With them
    ``(grid_steps, live_steps)``: the tables' length, and how many of the
    tiles hold a (query, key) pair the in-tile masks allow (q >= k when
    causal, q - k < window); equal but for a row that no tile serves (lengths
    that differ), which still takes one step, on its last tile, all masked
    there: its block is written."""
    qi, ki = np.indices((n_q, n_k))
    most = qi * block_q + block_q - 1 - ki * block_k        # a tile's largest q - k
    least = qi * block_q - (ki * block_k + block_k - 1)     # and its smallest
    live = np.ones((n_q, n_k), bool)
    if causal:
        live = most >= 0
        if window is not None:
            live &= least < window
    walked = live.T.copy() if key_major else live.copy()
    walked[~walked.any(axis=1), -1] = True
    rows, cols = np.nonzero(walked)                         # row by row, ascending
    edge = rows[1:] != rows[:-1]
    first, last = np.append(True, edge), np.append(edge, True)
    q_blocks, k_blocks = (cols, rows) if key_major else (rows, cols)
    return (tuple(t.astype(np.int32) for t in (q_blocks, k_blocks, first, last)),
            (len(rows), int(live.sum())))


def _tile_index_maps(rep: int):
    """Index maps of a q-shaped operand [B, Hq, Sq, *] and of K / V [B, Hkv,
    Sk, *], whose block a step takes from the walk's tables (scalar-prefetched
    refs trail the grid's indices). GQA in the plain, ``sel`` and ``blk``
    kernels and under a window with one head a group: the grid walks QUERY
    heads and a query head maps to its kv head in the index map, no repeated
    K/V materialization in HBM (a window's grouped queries walk KV heads:
    ``_fold_specs``)."""
    return (lambda bi, hi, t, qs, ks, *_: (bi, hi, qs[t], 0),
            lambda bi, hi, t, qs, ks, *_: (bi, hi // rep, ks[t], 0))


def _step(walk):
    """This grid step's (q block, k block, first of its row, last of its row),
    read from the walk's tables in SMEM."""
    t = pl.program_id(2)
    q_blocks, k_blocks, first, last = walk
    return q_blocks[t], k_blocks[t], first[t] == 1, last[t] == 1


def _allowed(s, q_start, k_start, causal, window, mask, q_axis: int):
    """Scores with what a query may not see set to NEG_INF. ``s`` is
    [block_q, block_k] (``q_axis`` 0) or its transpose; ``mask`` a block of
    the key sets in the same orientation, or None."""
    if causal:
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_ids - k_ids < window, s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask.astype(jnp.int32) != 0, s, NEG_INF)
    return s


def _band_seen(q_start, k_start, shape, window, q_axis: int):
    """Which (query, key) pairs of a tile a causal window allows, bool of
    ``shape`` ([queries, keys], or with ``q_axis`` 1 its transpose): positions
    do not depend on the head, so the folded kernels make it once a step at one
    head's shape and repeat it over the group."""
    ahead = ((q_start - k_start) + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    return (ahead >= 0) & (ahead < window)


def _set_slab(n_sets: int) -> int:
    """How many of a row's ``n_sets`` block flags a grid step takes: all of
    them, or where they are whole lane tiles the 128 that hold the tile's."""
    return 128 if n_sets > 128 and n_sets % 128 == 0 else n_sets


def _expand_sets(slab, ki, block_k: int, set_block: int, key_major: bool):
    """A tile of the key sets from block flags: ``slab`` [block_q, W] int8 holds
    a flag a query row and block of ``set_block`` keys, the tile's own
    ``block_k / set_block`` of them from lane ``ki * that % W`` on. Each flag is
    spread over its block's keys by a 0/1 product (exact), query-major
    [block_q, block_k] or ``key_major`` its transpose; int32, non-zero: allowed."""
    width = slab.shape[1]
    first = (ki * (block_k // set_block)) % width
    flags = slab.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    shape = (block_k, width) if key_major else (width, block_k)
    key = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if key_major else 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if key_major else 0)
    spread = jnp.where(lane == first + key // set_block, 1.0, 0.0).astype(jnp.bfloat16)
    dims = (((1,), (1,)), ((), ())) if key_major else (((1,), (0,)), ((), ()))
    operands = (spread, flags) if key_major else (flags, spread)
    return (jax.lax.dot_general(*operands, dims, preferred_element_type=jnp.float32)
            > 0.5).astype(jnp.int32)


def _tile_mask(mask_ref, ki, block_k, set_block, key_major):
    """The tile of key sets a step masks by: none, the operand's own block (one
    set a query row, [Sq, Sk]), or block flags spread out (``set_block``)."""
    if mask_ref is None:
        return None
    if set_block is None:
        return mask_ref[0]
    return _expand_sets(mask_ref[0, 0], ki, block_k, set_block, key_major)


def _flash_kernel(
    walk, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, block_q, block_k, window=None, set_block=None
):
    qi, ki, first, last = _step(walk)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Keep q/k/v in bf16 for the MXU (f32 inputs would run the MXU at a
    # fraction of peak); accumulate in f32 via preferred_element_type.
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    if causal and window is None and mask_ref is None:
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
    else:
        s = _allowed(s, q_start, k_start, causal, window,
                     _tile_mask(mask_ref, ki, block_k, set_block, False), 0)
    m_prev = m_ref[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1] -> broadcast over lanes
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_ref.shape
    )
    acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[:] = m_new

    @pl.when(last)
    def _final():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp for the backward kernels, replicated along lanes
            # as m and l are ([B,H,S,128]); the vjp rule keeps one lane.
            lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _fit_block(requested: int, seq: int, multiple: int = 16) -> int:
    """Largest block <= requested that divides ``seq`` and is a multiple
    of the bf16 sublane tile (16) — so e.g. S=1536 stays on the Pallas
    kernel with 512-wide blocks instead of silently falling back to the
    unblocked reference when the default block does not divide it."""
    b = min(requested, seq)
    while b >= multiple and (seq % b or b % multiple):
        b -= multiple
    return max(b, multiple)


def _variant(window, mask, set_block=None) -> str | None:
    """What tells a kernel variant's calls apart on the op line: None for the
    plain kernels (``flash_fwd`` ...), ``win`` for a window (``attn_win_fwd``
    ...), ``sel`` for a key set a query row (``attn_sel_fwd`` ...), ``blk`` for
    a set a query row and kv head by blocks (``attn_blk_fwd`` ...)."""
    if set_block is not None:
        return "blk"
    return "sel" if mask is not None else "win" if window is not None else None


def _kept_pairs(sq: int, sk: int, causal: bool, window, top_k) -> float:
    """(query, key) pairs a head computes usefully: the causal triangle, a
    window's band, or ``top_k`` keys a query where it has more."""
    if not causal:
        return float(sq * sk)
    width = sk if window is None else window
    if top_k is not None:
        width = min(width, top_k)
    return float(sum(min(t + 1 + sk - sq, width) for t in range(sq)))


def _win_geometry(variant, rep: int, block_q: int, keys: int, steps) -> dict:
    """What the ``attn_win_*`` entries of ``kernel_costs()`` say of a call's
    tiling: the query heads a tile holds, a step's [rows, keys], and the pairs
    a head's steps walk, beside the kept pairs its FLOPs count."""
    if variant != "win":
        return {}
    return dict(heads_a_tile=rep, tiles=[rep * block_q, keys],
                walked_pairs=float(steps[0] * block_q * keys))


def _set_spec(n_sets: int, rep: int, block_q: int, block_k: int, set_block: int):
    """The block flags [B, Hkv, Sq, Sk / set_block] int8 a step takes, for every
    kernel and both walks: the query block's rows of the query head's kv head,
    and of a row's flags the slab that holds the key block's (``_set_slab``;
    it changes every ``slab x set_block`` keys, so a query block's steps
    mostly keep the one they have)."""
    slab, per_tile = _set_slab(n_sets), block_k // set_block
    if slab % per_tile:
        raise ValueError(f"a key block's {per_tile} flags straddle slabs of {slab}")
    return pl.BlockSpec(
        (1, 1, block_q, slab),
        lambda bi, hi, t, qs, ks, *_: (bi, hi // rep, qs[t], ks[t] * per_tile // slab))


def _flash_forward(
    q,
    k,
    v,
    mask=None,
    *,
    causal: bool,
    sm_scale: float | None,
    block_q: int,
    block_k: int,
    interpret: bool,
    save_residuals: bool = False,
    window: int | None = None,
    top_k: int | None = None,
    set_block: int | None = None,
):
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[2]
    dv = v.shape[3]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    rep = hq // hkv
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk, set_block or 16)
    variant = _variant(window, mask, set_block)
    # fallback for shapes the TPU tiling can't take: ragged blocks or blocks
    # not multiple of the bf16 sublane tile (16)
    if sq % block_q or sk % block_k or block_q % 16 or block_k % 16:
        note_kernel_trace("flash_attention", "mha_reference")
        sets = {"mask": mask} if set_block is None else {"block_sets": mask,
                                                         "set_block": set_block}
        o = mha_reference(q, k, v, causal=causal, sm_scale=scale, window=window, **sets)
        return (o, None) if save_residuals else o
    note_kernel_trace("flash_attention", "interpret" if interpret else "pallas")
    if _folds(variant, rep, block_q, interpret):
        return _fold_forward(q, k, v, scale=scale, block_q=block_q, block_k=block_k,
                             window=window, interpret=interpret,
                             save_residuals=save_residuals)
    walk, steps = _tile_walk(sq // block_q, sk // block_k, block_q, block_k, causal, window)
    if variant is None and dv == d:
        note_flash_cost("flash_fwd", q, k, causal=causal, residuals=save_residuals, steps=steps)
    elif set_block is not None:
        note_block_set_cost("fwd", q, k, v, (block_q, block_k), _set_slab(mask.shape[3]),
                            residuals=save_residuals, steps=steps)
    else:
        note_attention_cost("fwd", variant, q, k, v,
                            _kept_pairs(sq, sk, causal, window, top_k),
                            residuals=save_residuals, masked=mask is not None, steps=steps,
                            **_win_geometry(variant, 1, block_q, block_k, steps))
    inner = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
        set_block=set_block,
    )

    def kernel(*refs):
        walk, (q_ref, k_ref, v_ref), refs = refs[:4], refs[4:7], refs[7:]
        mask_ref, refs = (refs[0], refs[1:]) if mask is not None else (None, refs)
        o_ref, lse_ref, refs = ((refs[0], refs[1], refs[2:]) if save_residuals
                                else (refs[0], None, refs[1:]))
        inner(walk, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *refs)

    q_index, kv_index = _tile_index_maps(rep)
    out_specs = [pl.BlockSpec((1, 1, block_q, dv), q_index)]
    out_shape = [jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype)]
    if save_residuals:
        out_specs.append(pl.BlockSpec((1, 1, block_q, 128), q_index))
        out_shape.append(jax.ShapeDtypeStruct((b, hq, sq, 128), jnp.float32))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_index),
        pl.BlockSpec((1, 1, block_k, d), kv_index),
        pl.BlockSpec((1, 1, block_k, dv), kv_index),
    ]
    operands = (q, k, v)
    if set_block is not None:
        in_specs.append(_set_spec(mask.shape[3], rep, block_q, block_k, set_block))
        operands += (mask,)
    elif mask is not None:
        # one key set a query row, shared by the heads of a batch row
        in_specs.append(pl.BlockSpec((1, block_q, block_k),
                                     lambda bi, hi, t, qs, ks, *_: (bi, qs[t], ks[t])))
        operands += (mask,)
    result = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hq, steps[0]),
            in_specs=in_specs,
            out_specs=out_specs if save_residuals else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape if save_residuals else out_shape[0],
        interpret=interpret,
        name="flash_fwd" if variant is None else f"attn_{variant}_fwd",
    )(*walk, *operands)
    # The kernel writes lse replicated over 128 lanes; one lane is the
    # residual and what the backward kernels read (S minor: a trailing
    # 1 would be padded back to 128 lanes in HBM).
    return (result[0], result[1][..., 0]) if save_residuals else result


def _bwd_probs_t(q, k, v, g, lse, delta, *, sm_scale, causal, q_start, k_start,
                 window=None, mask_t=None, fold=None):
    """One [block_k, block_q] tile of the backward pass, k-major: P^T and
    dS^T. Scores are taken transposed (K Q^T) so that the per-query
    statistics ``lse`` and ``delta`` are [1, block_q] ROWS, broadcast
    along sublanes: compact in HBM, where a column per query would be
    padded to 128 lanes (67 MB a layer at 2 x 16 x 4096, not 0.5).
    ``fold`` (rep, block_q): the queries are a kv head's ``rep`` query heads
    x one query block, head-major along the lanes, under a window; the
    band's mask is made once at [block_k, block_q] and repeated a head."""
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if fold is not None:
        rep, block_q = fold
        seen = _band_seen(q_start, k_start, (k.shape[0], block_q), window, 1)
        s_t = s_t + jnp.tile(jnp.where(seen, 0.0, NEG_INF), (1, rep))
    elif window is not None or mask_t is not None:
        s_t = _allowed(s_t, q_start, k_start, causal, window, mask_t, 1)
    elif causal:
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
        s_t = jnp.where(q_ids >= k_ids, s_t, NEG_INF)
    p_t = jnp.exp(s_t - lse)  # masked entries underflow to 0
    dp_t = jax.lax.dot_general(
        v, g, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds_t = (p_t * (dp_t - delta) * sm_scale).astype(q.dtype)
    return p_t, ds_t


def _bwd_dq_kernel(walk, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref, dq_ref,
                   acc_ref, *, sm_scale, causal, block_q, block_k, window=None,
                   set_block=None):
    """dQ: for one q block, accumulate dS @ K over its k blocks (the walk is
    query-major: a row's tiles run in sequence on-core, acc lives in VMEM)."""
    qi, ki, first, last = _step(walk)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k = k_ref[0, 0]
    _, ds_t = _bwd_probs_t(
        q_ref[0, 0], k, v_ref[0, 0], g_ref[0, 0], lse_ref[0, 0, 0],
        delta_ref[0, 0, 0], sm_scale=sm_scale, causal=causal,
        q_start=qi * block_q, k_start=ki * block_k, window=window,
        mask_t=_tile_mask(mask_ref, ki, block_k, set_block, True))
    acc_ref[:] += jax.lax.dot_general(
        ds_t, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # [bq, d]

    @pl.when(last)
    def _final():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(walk, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc,
                     *, sm_scale, causal, block_q, block_k, window=None, set_block=None):
    """dK/dV: for one k block, accumulate over its q blocks (the walk is
    key-major). P^T and dS^T come out k-major, so both products are
    plain [bk, bq] @ [bq, d] — no transposes materialize."""
    qi, ki, first, last = _step(walk)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]
    g = g_ref[0, 0]
    p_t, ds_t = _bwd_probs_t(
        q, k_ref[0, 0], v_ref[0, 0], g, lse_ref[0, 0, 0], delta_ref[0, 0, 0],
        sm_scale=sm_scale, causal=causal, q_start=qi * block_q, k_start=ki * block_k,
        window=window, mask_t=_tile_mask(mask_ref, ki, block_k, set_block, True))
    dv_acc[:] += jax.lax.dot(p_t.astype(g.dtype), g,
                             preferred_element_type=jnp.float32)  # [bk, dv]
    dk_acc[:] += jax.lax.dot(ds_t, q, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _final():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, mask=None, *, causal, sm_scale, block_q, block_k,
                    interpret, window=None, top_k=None, set_block=None):
    """Pallas dq/dk/dv. ``lse`` is the compact f32 [B, Hq, S] residual.
    K/V stay at kv-head count (GQA via index maps). The plain, ``sel`` and
    ``blk`` kernels write dk/dv at the q-head count and the sum over a group
    follows here; a window's grouped queries take ``_fold_backward``, whose
    dk/dv leave the kernel at the kv-head count."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv_width = v.shape[3]
    rep = hq // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk, set_block or 16)
    n_q, n_k = sq // block_q, sk // block_k
    variant = _variant(window, mask, set_block)
    if _folds(variant, rep, block_q, interpret):
        return _fold_backward(q, k, v, o, lse, g, scale=scale, block_q=block_q,
                              block_k=block_k, window=window, interpret=interpret)
    # Per-query statistics as one [1, block_q] row per q block (a block
    # whose trailing dims are the array's own fits any block size): lse,
    # and delta = rowsum(dO * O).
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse = lse.reshape(b, hq, n_q, 1, block_q)
    delta = delta.reshape(b, hq, n_q, 1, block_q)

    # the same tiles in two orders: dQ's accumulator is a query block's,
    # dK/dV's a key block's
    dq_walk, dq_steps = _tile_walk(n_q, n_k, block_q, block_k, causal, window)
    dkdv_walk, dkdv_steps = _tile_walk(n_q, n_k, block_q, block_k, causal, window,
                                       key_major=True)
    if variant is None and dv_width == d:
        note_flash_cost("flash_bwd_dq", q, k, causal=causal, steps=dq_steps)
        note_flash_cost("flash_bwd_dkdv", q, k, causal=causal, steps=dkdv_steps)
    elif set_block is not None:
        tiles, slab = (block_q, block_k), _set_slab(mask.shape[3])
        note_block_set_cost("bwd_dq", q, k, v, tiles, slab, steps=dq_steps)
        note_block_set_cost("bwd_dkdv", q, k, v, tiles, slab, steps=dkdv_steps)
    else:
        pairs = _kept_pairs(sq, sk, causal, window, top_k)
        for part, steps in (("bwd_dq", dq_steps), ("bwd_dkdv", dkdv_steps)):
            note_attention_cost(part, variant, q, k, v, pairs, masked=mask is not None,
                                steps=steps,
                                **_win_geometry(variant, 1, block_q, block_k, steps))
    prefix = "flash" if variant is None else f"attn_{variant}"

    # one set of specs for both kernels: each reads its own walk's tables
    q_index, kv_index = _tile_index_maps(rep)
    # dK / dV leave at the query heads' count
    k_index = lambda bi, hi, t, qs, ks, *_: (bi, hi, ks[t], 0)  # noqa: E731
    row_spec = pl.BlockSpec((1, 1, 1, 1, block_q),
                            lambda bi, hi, t, qs, ks, *_: (bi, hi, qs[t], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_index),
        pl.BlockSpec((1, 1, block_k, d), kv_index),
        pl.BlockSpec((1, 1, block_k, dv_width), kv_index),
        pl.BlockSpec((1, 1, block_q, dv_width), q_index),
        row_spec, row_spec,
    ]
    operands = (q, k, v, g, lse, delta)
    if set_block is not None:
        # query-major as they are: a step spreads its flags key-major itself
        operands += (mask,)
        in_specs.append(_set_spec(mask.shape[3], rep, block_q, block_k, set_block))
    elif mask is not None:
        # k-major, as the tiles are: the transpose is an XLA pass over int8
        operands += (jnp.swapaxes(mask, 1, 2),)
        in_specs.append(pl.BlockSpec((1, block_k, block_q),
                                     lambda bi, hi, t, qs, ks, *_: (bi, ks[t], qs[t])))

    def call(kernel, walk, out_specs, out_shape, scratch_shapes, name):
        def body(*refs):
            ins, rest = refs[4:10], refs[10:]
            mask_ref, rest = (rest[0], rest[1:]) if mask is not None else (None, rest)
            kernel(refs[:4], *ins, mask_ref, *rest, sm_scale=scale, causal=causal,
                   block_q=block_q, block_k=block_k, window=window, set_block=set_block)

        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(b, hq, len(walk[0])), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            out_shape=out_shape,
            interpret=interpret,
            name=name,
        )(*walk, *operands)

    dq = call(
        _bwd_dq_kernel, dq_walk,
        pl.BlockSpec((1, 1, block_q, d), q_index),
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block_q, d), jnp.float32)],
        f"{prefix}_bwd_dq")
    dk, dv = call(
        _bwd_dkdv_kernel, dkdv_walk,
        [pl.BlockSpec((1, 1, block_k, d), k_index),
         pl.BlockSpec((1, 1, block_k, dv_width), k_index)],
        [jax.ShapeDtypeStruct((b, hq, sk, d), k.dtype),
         jax.ShapeDtypeStruct((b, hq, sk, dv_width), v.dtype)],
        [pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, dv_width), jnp.float32)],
        f"{prefix}_bwd_dkdv")
    if rep > 1:
        dk = dk.reshape(b, hkv, rep, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, rep, sk, dv_width).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# The window kernels under grouped queries (``window is not None and rep > 1``):
# the grid's head axis walks KV heads, and a tile's rows are the ``rep`` query
# heads of that kv head x one query block, [rep, block_q, D] -> [rep * block_q,
# D] in VMEM, against keys fetched once a group. Where a query block's whole
# band fits one [rows, keys] float32 tile of _BAND_TILE_BYTES it IS one tile
# (``_band_tile``: a step a query block, softmax direct, no rescale) for the
# forward and dQ; wider bands, and dK/dV always, walk key blocks.
_BAND_TILE_BYTES = 8 * 1024 * 1024
_FOLD_VMEM_LIMIT = 100 * 1024 * 1024  # a v5e core has 128 MiB; the default scope is 16


def _folds(variant, rep: int, block_q: int, interpret: bool) -> bool:
    """Whether a call takes the folded kernels: a window alone, grouped queries,
    and on the chip a query block of whole lane tiles (a head's queries are 128
    lanes or more of the backward tiles and of the logsumexp's row)."""
    return variant == "win" and rep > 1 and (interpret or block_q % 128 == 0)


def _band_tile(sq: int, sk: int, block_q: int, window: int, rep: int) -> int | None:
    """Keys of the one tile that holds the whole band of a query block of the
    folded window kernels (``block_q + window - 1`` keys, in whole query
    blocks), or None where the band is walked in key blocks."""
    band = min(-(-(block_q + window - 1) // block_q) * block_q, sk)
    return band if sq == sk and rep * block_q * band * 4 <= _BAND_TILE_BYTES else None


def _fold_walk(sq, sk, block_q, block_k, window, band):
    """The query-major walk of the folded forward and dQ: ``_tile_walk``'s, or
    where the band is one tile one step a query block, whose key column counts
    QUERY blocks (K and V are fetched at an offset of so many, the band's
    start, not by blocks of their own size; ``band``: ``_band_tile``'s). With
    it ``(grid_steps, live_steps)``, the keys of a tile and the keys its column
    counts by."""
    if band is None:
        return *_tile_walk(sq // block_q, sk // block_k, block_q, block_k, True,
                           window), block_k, block_k
    qs = np.arange(sq // block_q, dtype=np.int32)
    starts = np.clip(qs + 1 - band // block_q, 0, (sk - band) // block_q).astype(np.int32)
    return ((qs, starts, np.ones_like(qs), np.ones_like(qs)), (len(qs), len(qs)), band,
            block_q)


def _fold_specs(rep, block_q, d, dv, keys, unit):
    """Block specs of the folded kernels' grid (batch, KV head, step): a
    q-shaped operand viewed [B, Hkv, rep, S, *], K and V tiles of ``keys`` keys
    from key ``unit`` x the walk's key column on, a row of per-query
    statistics [B, Hkv, n_q, 1, rep * block_q]."""
    group = lambda bi, hi, t, qs, ks, *_: (bi, hi, 0, qs[t], 0)  # noqa: E731
    at = lambda bi, hi, t, qs, ks, *_: (bi, hi, ks[t] * unit, 0)  # noqa: E731
    kv = lambda width: pl.BlockSpec(  # noqa: E731
        (pl.Squeezed(), pl.Squeezed(), pl.Element(keys), pl.Element(width)), at)
    return dict(
        q=pl.BlockSpec((1, 1, rep, block_q, d), group),
        o=pl.BlockSpec((1, 1, rep, block_q, dv), group),
        k=kv(d), v=kv(dv),
        row=pl.BlockSpec((1, 1, 1, 1, rep * block_q),
                         lambda bi, hi, t, qs, ks, *_: (bi, hi, qs[t], 0, 0)))


def _group_rows(ref):
    """A folded q-shaped block [1, 1, rep, block_q, D] as the tile's rows
    [rep * block_q, D]: free where block_q is whole sublane tiles."""
    rep, block_q, d = ref.shape[2:]
    return ref[0, 0].reshape(rep * block_q, d)


def _to_group_rows(x, b, hkv, rep, n_q, block_q):
    """Per-query statistics [B, Hq, S] as the folded kernels' rows [B, Hkv,
    n_q, 1, rep * block_q] (head-major inside a query block)."""
    x = x.reshape(b, hkv, rep, n_q, block_q).transpose(0, 1, 3, 2, 4)
    return x.reshape(b, hkv, n_q, 1, rep * block_q)


def _from_group_rows(x, b, hkv, rep, n_q, block_q):
    x = x.reshape(b, hkv, n_q, rep, block_q).transpose(0, 1, 3, 2, 4)
    return x.reshape(b, hkv * rep, n_q * block_q)


def _fold_fwd_kernel(walk, q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                     sm_scale, block_q, unit, window, rep, single):
    qi, ki, first, last = _step(walk)
    keys, rows = k_ref.shape[0], rep * block_q
    v = v_ref[...]
    s = jax.lax.dot_general(_group_rows(q_ref), k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    seen = _band_seen(qi * block_q, ki * unit, (block_q, keys), window, 0)
    s = jnp.where(seen[None], s.reshape(rep, block_q, keys), NEG_INF).reshape(rows, keys)

    def write(o, lse):
        o_ref[0, 0] = o.reshape(rep, block_q, v.shape[1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # a column a query -> the compact row the backward kernels read
            lse_ref[0, 0, 0] = jnp.transpose(jnp.broadcast_to(lse, (rows, 128)))[:1]

    m_cur = jnp.max(s, axis=1, keepdims=True)
    if single:  # the whole band: softmax direct, no accumulator
        p = jnp.exp(s - m_cur)
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        write(o / l_cur, m_cur + jnp.log(jnp.maximum(l_cur, 1e-30)))
        return
    acc_ref, m_ref, l_ref = scratch

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True),
                                                   l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(last)
    def _final():
        write(acc_ref[:] / l_ref[:, :1],
              m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30)))


def _fold_forward(q, k, v, *, scale, block_q, block_k, window, interpret, save_residuals):
    """The window variant's forward under grouped queries. Returns ``o``, or
    with ``save_residuals`` ``(o, lse)``, the logsumexp compact [B, Hq, S]."""
    b, hq, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    rep, n_q = hq // hkv, sq // block_q
    rows = rep * block_q
    band = _band_tile(sq, sk, block_q, window, rep)
    walk, steps, keys, unit = _fold_walk(sq, sk, block_q, block_k, window, band)
    note_attention_cost("fwd", "win", q, k, v, _kept_pairs(sq, sk, True, window, None),
                        residuals=save_residuals, steps=steps,
                        **_win_geometry("win", rep, block_q, keys, steps))
    specs = _fold_specs(rep, block_q, d, dv, keys, unit)
    out_specs, out_shape = [specs["o"]], [jax.ShapeDtypeStruct((b, hkv, rep, sq, dv), q.dtype)]
    if save_residuals:
        out_specs.append(specs["row"])
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, n_q, 1, rows), jnp.float32))

    def kernel(*refs):
        lse_ref, scratch = (refs[8], refs[9:]) if save_residuals else (None, refs[8:])
        _fold_fwd_kernel(refs[:4], *refs[4:8], lse_ref, *scratch, sm_scale=scale,
                         block_q=block_q, unit=unit, window=window, rep=rep,
                         single=band is not None)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, hkv, steps[0]),
            in_specs=[specs["q"], specs["k"], specs["v"]], out_specs=out_specs,
            scratch_shapes=[] if band else [pltpu.VMEM((rows, dv), jnp.float32),
                                            pltpu.VMEM((rows, 128), jnp.float32),
                                            pltpu.VMEM((rows, 128), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_FOLD_VMEM_LIMIT),
        interpret=interpret,
        name="attn_win_fwd",
    )(*walk, q.reshape(b, hkv, rep, sq, d), k, v)
    o = out[0].reshape(b, hq, sq, dv)
    return (o, _from_group_rows(out[1], b, hkv, rep, n_q, block_q)) if save_residuals else o


def _fold_dq_kernel(walk, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, *scratch,
                    sm_scale, block_q, unit, window, rep, single):
    """dQ of a group's query block: dS @ K over its band, one tile or a walk
    of key blocks accumulated in VMEM."""
    qi, ki, first, last = _step(walk)
    k = k_ref[...]
    _, ds_t = _bwd_probs_t(
        _group_rows(q_ref), k, v_ref[...], _group_rows(g_ref), lse_ref[0, 0, 0],
        delta_ref[0, 0, 0], sm_scale=sm_scale, causal=True, q_start=qi * block_q,
        k_start=ki * unit, window=window, fold=(rep, block_q))
    dq = jax.lax.dot_general(ds_t, k, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # [rows, d]
    if single:
        dq_ref[0, 0] = dq.reshape(dq_ref.shape[2:]).astype(dq_ref.dtype)
        return
    acc_ref, = scratch

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += dq

    @pl.when(last)
    def _final():
        dq_ref[0, 0] = acc_ref[:].reshape(dq_ref.shape[2:]).astype(dq_ref.dtype)


def _fold_dkdv_kernel(walk, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                      dk_acc, dv_acc, *, sm_scale, block_q, block_k, window, rep):
    """dK/dV of a key block: the contractions run over the group's heads and
    the block's queries at once, so the sum over a group happens in the
    float32 accumulators and dK / dV leave at the KV heads' count."""
    qi, ki, first, last = _step(walk)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, g = _group_rows(q_ref), _group_rows(g_ref)
    p_t, ds_t = _bwd_probs_t(
        q, k_ref[0, 0], v_ref[0, 0], g, lse_ref[0, 0, 0], delta_ref[0, 0, 0],
        sm_scale=sm_scale, causal=True, q_start=qi * block_q, k_start=ki * block_k,
        window=window, fold=(rep, block_q))
    dv_acc[:] += jax.lax.dot(p_t.astype(g.dtype), g, preferred_element_type=jnp.float32)
    dk_acc[:] += jax.lax.dot(ds_t, q, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _final():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _fold_backward(q, k, v, o, lse, g, *, scale, block_q, block_k, window, interpret):
    """The window variant's dq / dk / dv under grouped queries; dK and dV come
    out at the KV heads' count, [B, Hkv, S, *]."""
    b, hq, sq, d = q.shape
    hkv, sk, dv_width = k.shape[1], k.shape[2], v.shape[3]
    rep, n_q, n_k = hq // hkv, sq // block_q, sk // block_k
    rows = rep * block_q
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse, delta = (_to_group_rows(x, b, hkv, rep, n_q, block_q) for x in (lse, delta))
    band = _band_tile(sq, sk, block_q, window, rep)
    dq_walk, dq_steps, keys, unit = _fold_walk(sq, sk, block_q, block_k, window, band)
    dkdv_walk, dkdv_steps = _tile_walk(n_q, n_k, block_q, block_k, True, window,
                                       key_major=True)
    pairs = _kept_pairs(sq, sk, True, window, None)
    for part, steps, tile_keys in (("bwd_dq", dq_steps, keys), ("bwd_dkdv", dkdv_steps, block_k)):
        note_attention_cost(part, "win", q, k, v, pairs, steps=steps,
                            **_win_geometry("win", rep, block_q, tile_keys, steps))
    grouped = lambda x: x.reshape(b, hkv, rep, sq, x.shape[3])  # noqa: E731
    operands = (grouped(q), k, v, grouped(g), lse, delta)
    params = dict(compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_FOLD_VMEM_LIMIT),
                  interpret=interpret)

    def body(kernel, **static):
        return lambda *refs: kernel(refs[:4], *refs[4:], sm_scale=scale, block_q=block_q,
                                    window=window, rep=rep, **static)

    specs = _fold_specs(rep, block_q, d, dv_width, keys, unit)
    dq = pl.pallas_call(
        body(_fold_dq_kernel, single=band is not None, unit=unit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, hkv, dq_steps[0]),
            in_specs=[specs["q"], specs["k"], specs["v"], specs["o"], specs["row"],
                      specs["row"]],
            out_specs=specs["q"],
            scratch_shapes=[] if band else [pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, sq, d), q.dtype),
        name="attn_win_bwd_dq", **params,
    )(*dq_walk, *operands).reshape(q.shape)
    block = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, block_k, width), lambda bi, hi, t, qs, ks, *_: (bi, hi, ks[t], 0))
    dk, dv = pl.pallas_call(
        body(_fold_dkdv_kernel, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, hkv, dkdv_steps[0]),
            in_specs=[specs["q"], block(d), block(dv_width), specs["o"], specs["row"],
                      specs["row"]],
            out_specs=[block(d), block(dv_width)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, dv_width), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        name="attn_win_bwd_dkdv", **params,
    )(*dkdv_walk, *operands)
    return dq, dk, dv


def _mha_backward_blocked(q, k, v, g, *, causal, sm_scale, block_q):
    """Flash-style blocked attention backward in plain JAX.

    Scans over q chunks, recomputing softmax per chunk — peak extra memory
    is O(block_q × S) per step instead of O(S²), which is what lets a
    1B-param model train at 8×2048 tokens on one 16 GB v5e chip.
    All heads already expanded (GQA handled by caller).
    """
    b, h, s, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_q = _fit_block(block_q, s)
    if s % block_q:
        block_q = s  # unblocked fallback for ragged sizes
    nq = s // block_q
    k_pos = jnp.arange(s)

    def body(carry, xs):
        dk_acc, dv_acc = carry
        q_blk, g_blk, q0 = xs  # [B,H,bq,D], [B,H,bq,D], scalar block start
        # bf16 operands on every dot (f32 inputs would cripple the MXU);
        # f32 accumulation via preferred_element_type.
        sblk = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k,
                          preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q0 + jnp.arange(block_q)
            mask = q_pos[:, None] >= k_pos[None, :]
            sblk = jnp.where(mask[None, None], sblk, NEG_INF)
        p = jax.nn.softmax(sblk, axis=-1)
        pb = p.astype(q.dtype)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_blk, v,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))).astype(q.dtype)
        dq_blk = jnp.einsum("bhqk,bhkd->bhqd", ds, k,
                            preferred_element_type=jnp.float32) * scale
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, q_blk,
                                     preferred_element_type=jnp.float32) * scale
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", pb, g_blk,
                                     preferred_element_type=jnp.float32)
        return (dk_acc, dv_acc), dq_blk

    q_blocks = q.reshape(b, h, nq, block_q, d).transpose(2, 0, 1, 3, 4)
    g_blocks = g.reshape(b, h, nq, block_q, d).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(nq) * block_q
    (dk, dv), dq_blocks = jax.lax.scan(
        body,
        (jnp.zeros((b, h, s, d), jnp.float32), jnp.zeros((b, h, s, d), jnp.float32)),
        (q_blocks, g_blocks, starts),
    )
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _blocks_fit(sq, sk, block_q, block_k, set_block=None) -> bool:
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk, set_block or 16)
    return not (sq % block_q or sk % block_k or block_q % 16 or block_k % 16)


@functools.lru_cache(maxsize=None)
def _make_flash(causal, sm_scale, block_q, block_k, interpret, window=None, top_k=None,
                masked=False, with_lse=False, set_block=None):
    """custom_vjp wrapper: Pallas kernels for BOTH directions (forward
    saves the logsumexp residual; dq and dk/dv are dedicated kernels).
    Ragged shapes fall back to the jnp blocked paths.

    The fwd rule names what the backward kernels need beyond q/k/v:
    the output is ``attn_out`` (one variable serves as primal output and
    residual, so a policy that saves the name keeps one copy) and the
    logsumexp is ``attn_lse``, compact f32 [B, Hq, S], which is also the
    form the backward kernels read. A ``jax.checkpoint`` policy that
    saves both names (remat ``attn`` in models/llama.py) runs
    ``flash_fwd`` once a layer; one that saves neither recomputes it in
    the backward pass, as before.

    ``masked``: the function takes a fourth operand, the key sets [B, Sq,
    Sk] int8 (with ``set_block`` block flags [B, Hkv, Sq, Sk / set_block]),
    which gets no cotangent. ``with_lse``: it returns ``(o, lse)``,
    the logsumexp [B, Hq, S] as a constant (its cotangent is dropped)."""
    static = dict(causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  interpret=interpret, window=window, top_k=top_k, set_block=set_block)

    @jax.custom_vjp
    def f(q, k, v, *mask):
        if with_lse:
            return _flash_forward(q, k, v, *mask, save_residuals=True, **static)
        return _flash_forward(q, k, v, *mask, **static)

    def fwd(q, k, v, *mask):
        if not _blocks_fit(q.shape[2], k.shape[2], block_q, block_k, set_block):
            if with_lse:
                raise NotImplementedError("the logsumexp comes from blocks that fit")
            return checkpoint_name(f(q, k, v, *mask), "attn_out"), (q, k, v, None, None, mask)
        o, lse = _flash_forward(q, k, v, *mask, save_residuals=True, **static)
        o = checkpoint_name(o, "attn_out")
        lse = checkpoint_name(lse, "attn_lse")
        return ((o, lse) if with_lse else o), (q, k, v, o, lse, mask)

    def bwd(res, g):
        q, k, v, o, lse, mask = res
        if with_lse:
            g = g[0]
        no_grad = tuple(None for _ in mask)
        if lse is not None:
            return _flash_backward(q, k, v, o, lse, g, *mask, **static) + no_grad
        if window is not None or masked or v.shape[3] != q.shape[3]:
            raise NotImplementedError(
                "a window, a key set or a narrower value head needs blocks that fit")
        # Ragged fallback: blocked-recompute backward in plain JAX.
        hq, hkv = q.shape[1], k.shape[1]
        if hq != hkv:
            rep = hq // hkv
            k_full = jnp.repeat(k, rep, axis=1)
            v_full = jnp.repeat(v, rep, axis=1)
        else:
            k_full, v_full = k, v
        dq, dk, dv = _mha_backward_blocked(
            q, k_full, v_full, g, causal=causal, sm_scale=sm_scale, block_q=block_q
        )
        if hq != hkv:
            b, _, s, d = dk.shape
            dk = dk.reshape(b, hkv, rep, s, d).sum(axis=2)
            dv = dv.reshape(b, hkv, rep, s, d).sum(axis=2)
        return dq, dk, dv

    f.defvjp(fwd, bwd)
    return f


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    window: int | None = None,
    mask=None,
    top_k: int | None = None,
    return_lse: bool = False,
    block_sets=None,
    set_block: int = 64,
):
    """Tiled attention. q [B,Hq,S,D], k [B,Hkv,S,D], v [B,Hkv,S,Dv] (GQA
    folded by repeat; the value head may be narrower than the key head).

    Differentiable (custom VJP); falls back to the interpreter off-TPU so
    tests run on the CPU mesh. Default 1024x1024 blocks: measured on v5e
    at head_dim 64 they run the fwd+bwd ~14% faster at seq 2k and ~46%
    faster at seq 32k than 512x512 (fewer per-block VPU rescales); 2048
    blocks exceed the 16 MiB scoped-VMEM stack limit.

    Three static facts give other kernels, told apart on the op line by
    their names; with none of them the kernels are the plain ones:
    ``window`` (causal only): query t sees keys t - window + 1 .. t, and the
    grid walks only the tiles the band meets (``attn_win_*``; with grouped
    queries, Hq > Hkv, the grid's head axis walks KV heads and a tile's rows are
    the group's query heads x ``block_q`` queries, the band's keys fetched once
    a group, as one tile where it fits ``_BAND_TILE_BYTES`` and else in blocks
    of ``block_k``, and dK / dV are summed over the group inside the kernel);
    ``mask``
    [B, Sq, Sk] int8: one key set a query row, shared by the heads of a
    batch row, under which the whole causal triangle's tiles are walked
    (``attn_sel_*``; ``top_k``, the most keys a set holds, only sizes the
    useful work that ``kernel_costs()`` records); ``block_sets`` [B, Hkv, Sq,
    Sk / set_block] int8: one key set a query row and kv head, a flag a block
    of ``set_block`` keys, spread over the block's keys inside each of the
    causal triangle's tiles, so that no [Sq, Sk] array exists (``attn_blk_*``).
    ``return_lse`` also returns each query's logsumexp over its keys,
    [B, Hq, S] float32.
    """
    if interpret is None:
        interpret = not on_tpu()
    if window is not None and not causal:
        raise ValueError("a window is a causal window")
    if block_sets is not None:
        if mask is not None or window is not None or return_lse or not causal:
            raise ValueError("block sets are causal and come alone")
        return _make_flash(causal, sm_scale, block_q, block_k, interpret, None, None, True,
                           False, set_block)(q, k, v, jax.lax.stop_gradient(block_sets))
    if mask is None and not return_lse:
        if window is None:
            return _make_flash(causal, sm_scale, block_q, block_k, interpret)(q, k, v)
        return _make_flash(causal, sm_scale, block_q, block_k, interpret, window)(q, k, v)
    masks = () if mask is None else (jax.lax.stop_gradient(mask),)
    return _make_flash(causal, sm_scale, block_q, block_k, interpret, window, top_k,
                       mask is not None, return_lse)(q, k, v, *masks)
