"""Paged-attention decode kernel, v2 "staging-buffer" design (Pallas TPU).

The framework's native answer to the decode kernel the reference buys
from vLLM (``python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py`` — the engine all of ``ray.llm`` delegates token
generation to). One decode step reads, per sequence, ONLY the KV pages
that hold live context: the sequence's block table is scalar-prefetched
into SMEM, and the kernel's input index maps walk it so the pipelined
HBM→VMEM copies fetch just the live pages, accumulating flash-style
online softmax per page block. HBM traffic per step is
``O(live_tokens)`` per SLOT — a dense gather pays the batch-max live
context for EVERY slot.

Why v2. The v1 kernel wrote the current token's K/V into the pool from
INSIDE the kernel through ``input_output_aliases`` — the only way to
mutate a loop-carried pool next to an opaque custom call without XLA
materializing a pool-sized copy per step. But the same pool buffer was
also a READ operand ``ppb`` more times (Mosaic can't DMA-slice
unaligned minor dims, so discontiguous pages ride separate BlockSpec
operands), and XLA cannot alias a buffer that is simultaneously donated
to an output and read through other operands: it inserted the defensive
copies anyway (~60 ms/step on a 1B model's 2 GB pool), and the kernel
lost to its own dense fallback.

v2 removes the conflict instead of fighting it:

  * **The pool is strictly READ-ONLY across the whole K-step fused
    dispatch.** No aliasing, no in-kernel writes, nothing for XLA to
    defend — the donated pool buffer passes through the decode scan
    untouched and un-copied.
  * **New tokens accumulate in a small staging carry**
    ``[L, slots, KH, SC, D]`` (SC = fused steps, padded to the sublane
    tile — KBs, not GBs). Step ``j`` writes each slot's fresh K/V at
    staging row ``j`` with a plain (cheap, tiny) XLA scatter; the
    kernel folds rows ``[0, j]`` into its online softmax as a SECOND KV
    source after the pool pages.
  * **ONE batched pool scatter per dispatch** (not per step) commits
    the staging buffer back at the dispatch boundary — by then the scan
    that read the pool has completed, so the donated buffer is updated
    in place.

Layout contract (matches ``llm/model.py``):

    k_pages / v_pages : [L, num_pages, KH, page_size, D]  (stacked pool;
                        a single-layer [num_pages, ...] pool is promoted)
    block_tables      : [slots, max_pages_per_seq] int32
    pos               : [slots] int32 — attend over [0, pos] inclusive
    q                 : [slots, KH, G, D]  (G = q heads per kv head)
    k_stage / v_stage : [Ls, slots, KH, SC, D] — staged tokens; row i of
                        slot s holds position ``base_s + i`` where
                        ``base_s = pos_s - stage_idx`` (the pool holds
                        [0, base_s) only)

Kernel structure:
  * grid = (slots, page_blocks), trailing axis sequential on-core so
    the f32 online-softmax state (m / l / acc scratch) carries across
    the page blocks of one sequence.
  * A grid step covers ``ppb`` pages (~256 tokens). Discontiguous pages
    can't ride one BlockSpec, so the pool is passed ``ppb`` times, each
    input's index map selecting one page of the block —
    auto-pipelining then double-buffers all of them. (Manual
    ``make_async_copy`` from HBM needs 128-aligned minor dims, which
    head_dim 64 models violate; pipelined copies don't.)
  * Dead blocks — past the live page count — clamp their index maps to
    the last live page. Pallas elides copies whose block index repeats,
    and ``pl.when`` skips the compute, so dead blocks cost neither
    bandwidth nor FLOPs.
  * The staging fold runs at the FINAL grid block: scores against the
    slot's [SC, D] staging rows, rows past ``stage_idx`` masked, then
    the normalize. Row ``stage_idx`` is the current token (always
    attended), so pos == 0 — where no pool block computes and
    m = -inf, l = 0 — still normalizes to exactly the staged value.
  * GQA without K/V replication: per kv head, q is [G, D] against the
    head's [T, D] page block (static loop over KH — decode is
    bandwidth-bound; MXU utilization is irrelevant here).

Off-TPU the kernel runs in interpreter mode (tests); the engine keeps
the dense path as the CPU default since interpret-mode decode is slow
(``llm/executor.resolve_attention_impl``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import on_tpu
from .trace_log import note_kernel_cost, note_kernel_trace

NEG_INF = -1e30

# Staging rows are the kernel block's sublane dim: keep them a multiple
# of the bf16 tile (16) so one padded size serves every pool dtype.
_STAGE_TILE = 16


def stage_rows(n_steps: int) -> int:
    """Padded staging-row count for an ``n_steps``-deep fused dispatch."""
    return max(_STAGE_TILE, -(-n_steps // _STAGE_TILE) * _STAGE_TILE)


def _decode_kernel(
    bt_ref,      # [slots, max_pages] int32 (SMEM, scalar-prefetched)
    base_ref,    # [slots] int32 — pool holds [0, base) per slot (SMEM)
    sl_ref,      # [1] int32 — staged rows [0, sl] are live (SMEM)
    l_ref,       # [1] int32 layer index (SMEM; consumed by index maps)
    q_ref,       # [1, KH, Gp, D] VMEM block
    ks_ref,      # [1, 1, KH, SC, D] this slot's staged K rows
    vs_ref,      # [1, 1, KH, SC, D] this slot's staged V rows
    *refs,       # ppb k-page refs, ppb v-page refs ([1, 1, KH, page, D]),
                 # then the output o, then scratch m/l/acc
    kh: int,
    page_size: int,
    ppb: int,
    n_blocks: int,
    scale: float,
):
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    o_ref, m_ref, lsum_ref, acc_ref = refs[2 * ppb:]
    si = pl.program_id(0)
    bi = pl.program_id(1)
    base = base_ref[si]
    # The pool holds positions [0, base) — everything newer rides the
    # staging rows and is folded below.
    n_live_pages = jax.lax.div(base + page_size - 1, page_size)
    needed = bi * ppb < n_live_pages

    @pl.when(bi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        lsum_ref[...] = jnp.zeros_like(lsum_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(needed)
    def _compute():
        t = ppb * page_size
        gp = q_ref.shape[2]
        # Token liveness within the block: global position < base
        # (strict — newer positions live in the staging rows).
        t_pos = bi * t + jax.lax.broadcasted_iota(jnp.int32, (gp, t), 1)
        live = t_pos < base

        for h in range(kh):
            q = q_ref[0, h]                                   # [Gp, D]
            kb = jnp.concatenate([r[0, 0, h] for r in k_refs])  # [T, D]
            vb = jnp.concatenate([r[0, 0, h] for r in v_refs])
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [Gp, T]
            # lax.select, not jnp.where: jnp's scalar-broadcast wrapper
            # lowers to a closed_call that trips a lowering-cache
            # KeyError (jax 0.9.0) when this kernel sits in an outer scan.
            s = jax.lax.select(live, s, jnp.full_like(s, NEG_INF))
            m_prev = m_ref[h]                                 # [Gp, 128]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            lsum_ref[h] = lsum_ref[h] * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=1, keepdims=True), lsum_ref[h].shape)
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot(
                p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(bi == n_blocks - 1)
    def _final():
        # Fold the staging rows (positions [base, base + sl], the last
        # being the in-flight token — always attended), then normalize.
        # Covers base == 0 too: no pool block ran (m = -inf, l = 0) and
        # the output reduces to softmax over the staged rows alone.
        sl = sl_ref[0]
        sc = ks_ref.shape[3]
        gp = q_ref.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (gp, sc), 1)
        live = row <= sl
        for h in range(kh):
            q = q_ref[0, h]                                   # [Gp, D]
            ks = ks_ref[0, 0, h]                              # [SC, D]
            vs = vs_ref[0, 0, h]
            s = jax.lax.dot_general(
                q, ks, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [Gp, SC]
            s = jax.lax.select(live, s, jnp.full_like(s, NEG_INF))
            m_prev = m_ref[h]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            lsum = lsum_ref[h] * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=1, keepdims=True), lsum_ref[h].shape)
            acc = acc_ref[h] * alpha[:, :1] + jax.lax.dot(
                p.astype(vs.dtype), vs, preferred_element_type=jnp.float32)
            o_ref[0, h] = (acc / lsum[:, :1]).astype(o_ref.dtype)


# NOTE: deliberately NOT @jax.jit-wrapped — a nested jit around a
# pallas_call inside an outer scan trips a lowering-cache KeyError in
# jax 0.9.0 ('closed_call' in cached_primitive_lowerings). Callers are
# always under jit themselves (decode_loop / decode_step).
def paged_decode_attention(
    q,
    k_pages,
    v_pages,
    block_tables,
    pos,
    k_cur=None,
    v_cur=None,
    *,
    page_size: int,
    pages_per_block: int | None = None,
    live_pages: int | None = None,
    layer=None,
    k_stage=None,
    v_stage=None,
    stage_idx=None,
    mesh=None,
    interpret: bool | None = None,
):
    """One decode step of attention over a read-only paged KV pool.

    q:            [slots, KH, G, D] — current-token queries, grouped by
                  kv head (``q.reshape(slots, KH, G, D)`` of the [H, D]
                  layout, matching ``llm/model.py``'s GQA grouping).
    k/v_pages:    [num_pages, KH, page_size, D] — one layer's pool — or
                  the FULL stacked pool [L, num_pages, KH, page_size, D]
                  with ``layer`` the (traced) layer index. Passing the
                  stacked pool lets the layer scan keep the pool in its
                  carry: the layer index rides the scalar prefetch into
                  the page index maps, so no [num_pages, ...] slice is
                  ever materialized. The pool is NEVER written here —
                  committing staged tokens back is the caller's
                  dispatch-boundary scatter (``llm/model.py``).
    block_tables: [slots, max_pages_per_seq] int32.
    pos:          [slots] int32 — attend over [0, pos] inclusive.

    Staging mode (the decode path): ``k_stage``/``v_stage``
    [Ls, slots, KH, SC, D] hold the tokens generated so far inside the
    current fused dispatch — row i of slot s is position
    ``pos_s - stage_idx + i`` — and ``stage_idx`` (traced scalar int32)
    says rows [0, stage_idx] are live, the last being the CURRENT
    token. The pool must hold [0, pos - stage_idx) only. ``Ls`` may be
    1 (per-layer staging) or the pool's L (layer-stacked staging
    indexed by ``layer``).

    Compat mode (kernel tests / one-off calls): without staging, the
    current token comes from ``k_cur``/``v_cur`` [slots, KH, D] (pool
    holds [0, pos)), or — when those are omitted too — is pulled back
    out of a pool that already holds position ``pos``. Both reduce to a
    single-row staging buffer internally.

    live_pages:   static upper bound on live POOL pages of ANY slot
                  (i.e. ``max(pos - stage_idx) // page_size + 1`` ≤
                  live_pages). Bounds the GRID, not just the copies:
                  without it, dead blocks still pay per-step pipeline
                  bookkeeping, so step count scales with pool capacity.
                  Callers should bucket it (powers of two) to bound
                  recompiles.

    mesh:         shard_map the kernel over the mesh's ``tp`` axis: the
                  pool/staging/q shard on their KV-head dim (the layout
                  ``llm/executor.py`` already gives them), each shard
                  runs the kernel on its local heads, and nothing is
                  gathered — attention is embarrassingly parallel over
                  KV heads. Manual over every mesh axis (Mosaic demands
                  it); operands are replicated over the axes the specs
                  do not name. Requires ``KH %% tp == 0``
                  (enforced by the executor). Used by PURE-tp meshes
                  only: pp meshes — composed pp×tp included — call the
                  kernel with ``mesh=None`` from inside
                  ``pp_model.pp_decode_loop``'s own manual region
                  (flattened over {"pp","tp"} when tp composes), where
                  every operand is already a local shard.

    Returns [slots, KH, G, D] in q.dtype.
    """
    if interpret is None:
        interpret = not on_tpu()
    note_kernel_trace("paged_decode_attention",
                      "interpret" if interpret else "pallas")
    squeeze_layer = k_pages.ndim == 4
    if squeeze_layer:
        k_pages = k_pages[None]
        v_pages = v_pages[None]
    layer = (jnp.zeros((1,), jnp.int32) if layer is None
             else jnp.asarray(layer, jnp.int32).reshape(1))
    n, kh, g, d = q.shape
    max_pages = block_tables.shape[1]
    if k_stage is not None:
        if k_cur is not None or stage_idx is None:
            raise ValueError("staging mode takes k_stage/v_stage/stage_idx "
                             "and no k_cur/v_cur")
        base = pos - jnp.asarray(stage_idx, jnp.int32)
        sl = jnp.asarray(stage_idx, jnp.int32).reshape(1)
    else:
        # Compat: single-row staging holding just the current token at
        # position ``pos``; the pool side masks strictly below it.
        base = pos
        sl = jnp.zeros((1,), jnp.int32)
        if k_cur is None:
            # Pool already holds position ``pos``: pull the token back
            # out so pool mask + staging fold give identical semantics.
            wp = jnp.take_along_axis(
                block_tables,
                jnp.minimum(pos // page_size, max_pages - 1)[:, None],
                axis=1)[:, 0]
            off = pos % page_size
            k_cur = k_pages[layer[0], wp, :, off]          # [slots, KH, D]
            v_cur = v_pages[layer[0], wp, :, off]
        k_stage = jnp.zeros((1, n, kh, _STAGE_TILE, d), k_pages.dtype
                            ).at[0, :, :, 0].set(k_cur.astype(k_pages.dtype))
        v_stage = jnp.zeros((1, n, kh, _STAGE_TILE, d), v_pages.dtype
                            ).at[0, :, :, 0].set(v_cur.astype(v_pages.dtype))
    stage_layers = k_stage.shape[0]
    sc = k_stage.shape[3]
    covered = max_pages if live_pages is None else min(live_pages, max_pages)
    # ~256 tokens of context per grid step: few enough steps that grid
    # overhead stays small, few enough inputs that VMEM stays bounded.
    if pages_per_block is None:
        pages_per_block = max(1, min(covered, 256 // page_size, 8))
    ppb = min(pages_per_block, covered)
    n_blocks = -(-covered // ppb)
    # per call, from the shapes: every slot attends over the live_pages
    # bound of pool context plus the staging rows (QK^T and PV), and reads
    # that much K and V once; what a slot really holds is a run-time value
    ctx = covered * page_size + sc
    note_kernel_cost(
        "paged_decode", 4.0 * n * kh * g * d * ctx,
        2 * n * kh * g * d * q.dtype.itemsize
        + 2 * n * kh * ctx * d * k_pages.dtype.itemsize)

    def _call(q, block_tables, base, sl, layer, k_stage, v_stage,
              k_pages, v_pages):
        # Shapes read here, not closed over: under shard_map this runs
        # per tp shard with the LOCAL KV-head count.
        n, kh, g, d = q.shape
        # Pad G to the f32 sublane tile (8) so scratch/compute rows are
        # aligned; padded q rows are zeros, their outputs sliced off.
        gp = -(-g // 8) * 8
        if gp != g:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

        def page_index_map(j):
            # Page j of block bi for slot si; dead/overflow indices clamp
            # to the last live page so consecutive steps repeat the block
            # index and Pallas skips the copy. (Scalar-prefetch refs
            # arrive as trailing index-map args; lax ops, not jnp — see
            # closed_call note above.)
            def index_map(si, bi, bt_ref, base_ref, sl_ref, l_ref):
                n_live = jax.lax.div(base_ref[si] + page_size - 1, page_size)
                logical = jax.lax.max(
                    jax.lax.min(bi * ppb + j,
                                jax.lax.min(n_live, max_pages) - 1), 0)
                return l_ref[0], bt_ref[si, logical], 0, 0, 0
            return index_map

        def stage_map(si, bi, bt_ref, base_ref, sl_ref, l_ref):
            # Per-layer staging (Ls == 1) clamps the layer index to 0.
            return jax.lax.min(l_ref[0], stage_layers - 1), si, 0, 0, 0

        page_block = (1, 1, kh, page_size, d)
        kernel = functools.partial(
            _decode_kernel,
            kh=kh,
            page_size=page_size,
            ppb=ppb,
            n_blocks=n_blocks,
            scale=d ** -0.5,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, n_blocks),
            in_specs=[
                pl.BlockSpec((1, kh, gp, d), lambda si, bi, *_: (si, 0, 0, 0)),
                pl.BlockSpec((1, 1, kh, sc, d), stage_map),
                pl.BlockSpec((1, 1, kh, sc, d), stage_map),
                *[pl.BlockSpec(page_block, page_index_map(j)) for j in range(ppb)],
                *[pl.BlockSpec(page_block, page_index_map(j)) for j in range(ppb)],
            ],
            out_specs=pl.BlockSpec((1, kh, gp, d),
                                   lambda si, bi, *_: (si, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kh, gp, 128), jnp.float32),
                pltpu.VMEM((kh, gp, 128), jnp.float32),
                pltpu.VMEM((kh, gp, d), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, kh, gp, d), q.dtype),
            interpret=interpret,
            name="paged_decode",
        )(block_tables, base, sl, layer,
          q, k_stage, v_stage,
          *([k_pages] * ppb), *([v_pages] * ppb))
        return out[:, :, :g] if gp != g else out

    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        # Every shard runs the identical kernel on its KV-head slice of
        # q/pool/staging — no collectives, attention is independent per
        # KV head. Manual over EVERY mesh axis: Mosaic refuses a kernel
        # under a region that leaves any axis to the partitioner ("cannot
        # be automatically partitioned"), even one of size 1; the specs
        # name tp only, so the other axes see replicated operands, which
        # is what the engine gives them.
        if kh % mesh.shape["tp"]:
            raise ValueError(
                f"n_kv_heads={kh} not divisible by tp={mesh.shape['tp']}")
        P = jax.sharding.PartitionSpec
        heads = P(None, "tp")                 # q [slots, KH, G, D]
        stacked = P(None, None, "tp")         # pool / staging [L, *, KH, ...]
        fn = jax.shard_map(
            _call, mesh=mesh,
            in_specs=(heads, P(), P(), P(), P(), stacked, stacked,
                      stacked, stacked),
            out_specs=heads,
            check_vma=False,
        )
        return fn(q, block_tables, base, sl, layer, k_stage, v_stage,
                  k_pages, v_pages)
    return _call(q, block_tables, base, sl, layer, k_stage, v_stage,
                 k_pages, v_pages)
