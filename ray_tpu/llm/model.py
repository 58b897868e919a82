"""Paged-KV prefill / decode forward passes.

TPU-native redesign of the paged attention the reference delegates to
vLLM (``python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:250``): the KV cache is a shared **page pool**

    k_pages / v_pages: [layers, num_pages, kv_heads, page_size, head_dim]

and each sequence owns an int32 **block table** of page indices. All shapes
are static — block tables are data, not shapes — so XLA compiles one
program per (chunk bucket) and one decode program total, while the
allocator moves pages between sequences at runtime (the property vLLM
gets from CUDA kernels, recovered here through gather/scatter that XLA
tiles natively).

Design points:
  * **Chunked prefill** (``prefill_chunk``): a prompt is processed in
    page-aligned chunks; each chunk attends over the pages written so far
    plus itself (causal), then scatters its K/V into the pool. Bounded
    chunk size keeps decode TTFT for other requests bounded — the
    reference's chunked-prefill scheduling.
  * **Prefix reuse**: a prompt whose leading blocks hash-match cached
    pages skips them entirely — the block table points at the shared
    pages read-only (engine-side trie + refcounting), and a partial
    tail-block match starts the suffix MID-page: the engine COW-forks
    the shared page first (``copy_pages``) and the chunk's row-granular
    ``(page, offset)`` scatter writes past the copied rows.
  * **Decode** (``decode_step``): one batched step over all slots;
    context K/V is read per-slot via the block tables. Inactive slots
    point at a per-slot trash page so their (ignored) writes never
    corrupt live pages — branchless, one compiled program for every
    occupancy.
  * **Paged v2 staging schedule** (``decode_loop(paged=True)``): the
    page pool is STRICTLY READ-ONLY across the whole K-step fused
    dispatch — the Pallas kernel only ever reads it, so XLA inserts no
    pool-sized copies around the custom call. Tokens generated inside
    the dispatch accumulate in a small ``[L, slots, KH, SC, D]`` staging
    carry (KBs, not GBs) the kernel folds into its online softmax as a
    second KV source, and ``commit_staging`` writes them back with ONE
    batched scatter at the dispatch boundary.
  * **The pool rides the layer scan as CARRY, never as scan xs.** The
    stacked pool is donated and updated in place layer by layer
    (``pool.at[l, pages, ...]``); gathers index ``pool[l, tables]``
    directly. Slicing the pool per layer as scan xs/ys (the obvious
    structure) makes XLA materialize pool-sized copies every layer of
    every step — measured ~45% of decode wall time at 2k capacity and
    ~3x total at 8k. Pool touches must stay at page granularity.
  * **Capacity-independent cost.** ``live_pages`` (a static,
    host-computed, power-of-two-bucketed bound on any slot's live page
    count) caps the attention width — gather or kernel grid — so a
    200-token batch costs the same under a 2k and an 8k ``max_len``.

Invariant (same as the reference's page model): before any step at
position ``pos``, pages hold K/V for ``[0, pos)``; the step writes
``pos`` and attends over ``[0, pos]``; garbage beyond ``pos`` is masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..models.llama import LlamaConfig
from ..observability.tracing import device_scope
from ..ops import apply_rope, rms_norm
from ..ops.paged_attention import paged_decode_attention, stage_rows


def init_pages(config: LlamaConfig, num_pages: int, page_size: int) -> dict:
    c = config
    shape = (c.n_layers, num_pages, c.n_kv_heads, page_size, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _project_qkv(h, layer):
    q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"])
    k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"])
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"])
    return q, k, v


def _mlp(x, layer, c: LlamaConfig, tp_axis: str | None = None):
    """SwiGLU MLP. ``tp_axis`` names a MANUAL mesh axis the mlp dim is
    sharded over (the flattened pp×tp region in ``pp_model``): gate/up
    are column-parallel (local), down is row-parallel — its partial
    output psums over the axis before rejoining the replicated residual.
    ``None`` (every auto-partitioned caller) is the unchanged path."""
    h = rms_norm(x, layer["mlp_norm"], eps=c.norm_eps)
    gate = jnp.einsum("bse,em->bsm", h, layer["w_gate"])
    up = jnp.einsum("bse,em->bsm", h, layer["w_up"])
    ff = jax.nn.silu(gate.astype(jnp.float32)).astype(c.dtype) * up
    down = jnp.einsum("bsm,me->bse", ff, layer["w_down"])
    if tp_axis is not None:
        down = lax.psum(down, tp_axis)
    return x + down


def _gather_ctx(pool, l, tables):
    """Layer-indexed page gather: pool [L, P, KH, page, D], tables
    [..., B] int32 -> [..., KH, B*page, D]. One gather op — the [P, ...]
    layer slice is never materialized."""
    g = pool[l, tables]                        # [..., B, KH, page, D]
    g = jnp.swapaxes(g, -4, -3)                # [..., KH, B, page, D]
    return g.reshape(*g.shape[:-3], -1, g.shape[-1])


@functools.partial(jax.jit,
                   static_argnames=("config", "page_size", "live_pages"),
                   donate_argnames=("pages",))
def prefill_chunk(params, pages: dict, block_table, tokens, start_pos,
                  config: LlamaConfig, page_size: int,
                  live_pages: int | None = None, lora=None, lora_slot=None):
    """Process one prompt chunk.

    tokens:      [C] int32 (static bucket size).
    block_table: [max_pages_per_seq] int32 — this sequence's pages.
    start_pos:   scalar int32. NOT required to be page-aligned: a
                 prefix-cache partial-block hit starts the suffix
                 mid-page (the engine COW-forks the shared page first),
                 so K/V lands via a row-granular (page, offset) scatter —
                 identical destinations to the old page-granular write
                 when the start IS aligned.
    live_pages:  static host-computed bound ≥ ``ceil(start_pos / page)``
                 — caps the context-gather width so chunk cost scales
                 with written context, not pool capacity.

    Attends over previously-written context ``[0, start_pos)`` (gathered
    via the block table; partial-page context rows are masked by
    position, so a mid-page start reads exactly the valid prefix rows)
    plus the chunk itself (causal), writes the chunk's K/V into its
    pages, and returns (pages, hidden [C, E]).
    """
    c = config
    C = tokens.shape[0]
    positions = start_pos + jnp.arange(C, dtype=jnp.int32)
    gather_table = block_table
    if live_pages is not None and live_pages < block_table.shape[0]:
        gather_table = block_table[:live_pages]
    max_ctx = gather_table.shape[0] * page_size
    ctx_pos = jnp.arange(max_ctx, dtype=jnp.int32)
    ctx_live = ctx_pos < start_pos                      # [ctx]
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    kh, g = c.n_kv_heads, c.n_heads // c.n_kv_heads
    # Row-granular write destinations: position p -> (its page, offset).
    # The clamp keeps pad rows past the table in range; they land at
    # future offsets of the last page, are masked (position > pos) until
    # decode overwrites them, and the engine clamps chunks so real
    # positions never exceed the table.
    write_pages = block_table[jnp.minimum(positions // page_size,
                                          block_table.shape[0] - 1)]  # [C]
    write_offs = positions % page_size                                # [C]

    x0 = params["embed"][tokens][None].astype(c.dtype)   # [1, C, E]

    def body(carry, xs):
        x, kf, vf = carry
        layer, l = xs
        h = rms_norm(x, layer["attn_norm"], eps=c.norm_eps)
        q, k, v = _project_qkv(h, layer)                # [1, H|KH, C, D]
        if lora is not None:
            # Prompt K/V must carry the adapter too (one adapter per
            # sequence — chunked prefill is single-sequence).
            from .lora import lora_delta_single

            def add(t, p, heads):
                d = lora_delta_single(h, lora[f"{p}.A"], lora[f"{p}.B"],
                                      l, lora_slot)
                return t + jnp.swapaxes(
                    d.reshape(1, C, heads, c.head_dim), 1, 2).astype(t.dtype)

            q = add(q, "wq", c.n_heads)
            k = add(k, "wk", c.n_kv_heads)
            v = add(v, "wv", c.n_kv_heads)
        q = apply_rope(q, positions, theta=c.rope_theta)
        k = apply_rope(k, positions, theta=c.rope_theta)
        ck = _gather_ctx(kf, l, gather_table)           # [KH, ctx, D]
        cv = _gather_ctx(vf, l, gather_table)
        qg = q[0].reshape(kh, g, C, c.head_dim)
        # context scores [KH, G, C, ctx] + in-chunk causal scores [.., C]
        s_ctx = jnp.einsum("kgcd,ktd->kgct", qg, ck).astype(jnp.float32)
        s_self = jnp.einsum("kgcd,ktd->kgct", qg, k[0]).astype(jnp.float32)
        scale = c.head_dim ** -0.5
        s_ctx = jnp.where(ctx_live[None, None, None], s_ctx * scale, -jnp.inf)
        s_self = jnp.where(causal[None, None], s_self * scale, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1)
        p_ctx, p_self = probs[..., :max_ctx].astype(c.dtype), probs[..., max_ctx:].astype(c.dtype)
        attn = jnp.einsum("kgct,ktd->kgcd", p_ctx, cv) + jnp.einsum(
            "kgct,ktd->kgcd", p_self, v[0])
        attn = attn.reshape(1, c.n_heads, C, c.head_dim)
        out = jnp.einsum("bhsd,hde->bse", attn, layer["wo"])
        if lora is not None:
            from .lora import lora_delta_single

            flat = jnp.swapaxes(attn, 1, 2).reshape(1, C, -1)
            out = out + lora_delta_single(
                flat, lora["wo.A"], lora["wo.B"], l, lora_slot).astype(out.dtype)
        x2 = _mlp(x + out, layer, c)
        # Row-granular scatter of the chunk's K/V: row j -> (page of
        # position start+j, its offset). Distinct in-range positions give
        # distinct (page, offset) pairs — no conflicts — and unlike the
        # old whole-page write this supports a mid-page chunk start
        # without clobbering a COW fork's copied prefix rows.
        kf = kf.at[l, write_pages, :, write_offs, :].set(
            jnp.swapaxes(k[0], 0, 1))
        vf = vf.at[l, write_pages, :, write_offs, :].set(
            jnp.swapaxes(v[0], 0, 1))
        return (x2, kf, vf), None

    (x, new_k, new_v), _ = lax.scan(
        body, (x0, pages["k"], pages["v"]),
        (params["layers"], jnp.arange(c.n_layers)))
    hidden = rms_norm(x, params["final_norm"], eps=c.norm_eps)[0]  # [C, E]
    return {"k": new_k, "v": new_v}, hidden


def decode_block(x, layer, kf, vf, l, block_tables, pos, write_idx,
                 c: LlamaConfig, page_size: int, paged: bool = False,
                 live_pages: int | None = None, lora=None, lora_idx=None,
                 stage=None, stage_step=None, stage_live=None,
                 attn_mesh=None, tp_axis: str | None = None):
    """One decoder block for a [n, 1, E] single-token batch against the
    FULL page pool (kf/vf: [L, P, KH, page, D]; ``l`` is this layer's
    index into it — traced, so the pool is only touched at gather/scatter
    granularity and updates stay in place). Shared by the unpipelined
    decode (``_decode_logits``) and the pp pipeline (``pp_model``) so the
    two paths stay bitwise-identical (greedy parity depends on it).
    Returns ``(x2, kf, vf, stage)``.

    ``paged=True`` routes context attention through the Pallas
    paged-attention kernel (``ops/paged_attention.py``): HBM traffic per
    step proportional to each slot's LIVE context. The v2 staging-buffer
    contract keeps the pool STRICTLY READ-ONLY around the kernel:

      * With ``stage=(k_stage, v_stage)`` (the fused decode loop) this
        layer's fresh K/V lands in staging row ``stage_step`` at layer
        ``l`` and the kernel folds rows [0, stage_step] as a second KV
        source; the pool is untouched — ``decode_loop`` commits the whole
        staging buffer with ONE batched scatter at the dispatch boundary.
      * Without ``stage`` (single-step ``decode_step``) the fresh K/V
        rides the kernel's compat path (``k_cur``/``v_cur``) and is
        scattered into the pool AFTER the kernel call — the pool is never
        simultaneously a kernel operand and a write target, so the
        donated buffer updates in place with no defensive copies.

    ``paged=False`` is the dense gather — width capped by ``live_pages``
    — kept as the CPU/test default and the numerical ground truth.
    ``attn_mesh`` (static) shard_maps the kernel over the mesh's tp axis
    (KV heads). ``tp_axis`` instead names a tp axis this block is ALREADY
    manual over (the flattened pp×tp region in ``pp_model``): the head
    dims of q/k/v/pool arrive pre-sharded, attention runs on the local
    heads with no collective, and the row-parallel ``wo`` output psums
    over the axis — so the KV-head count is read from the pool shard,
    never from the (global) config."""
    n = x.shape[0]
    # Local KV heads from the pool shard (== c.n_kv_heads everywhere
    # except inside a manual-tp region); the GQA ratio is tp-invariant.
    kh, g = kf.shape[2], c.n_heads // c.n_kv_heads
    offset = pos % page_size
    h = rms_norm(x, layer["attn_norm"], eps=c.norm_eps)
    q, k, v = _project_qkv(h, layer)                   # [n, H|KH, 1, D]
    if lora is not None:
        # Per-slot LoRA deltas on the attention projections (pre-rope):
        # each batch row gathers its adapter's A/B from the device stack
        # — batched multi-adapter decode in one compiled program (the
        # capability the reference buys from vLLM's SGMV kernels).
        from .lora import lora_delta

        def add(t, p, heads):
            d = lora_delta(h, lora[f"{p}.A"], lora[f"{p}.B"], l, lora_idx)
            return t + jnp.swapaxes(
                d.reshape(n, 1, heads, c.head_dim), 1, 2).astype(t.dtype)

        q = add(q, "wq", c.n_heads)
        k = add(k, "wk", c.n_kv_heads)
        v = add(v, "wv", c.n_kv_heads)
    q = apply_rope(q, pos[:, None], theta=c.rope_theta)
    k = apply_rope(k, pos[:, None], theta=c.rope_theta)
    qg = q[:, :, 0].reshape(n, kh, g, c.head_dim)
    if paged:
        k_tok, v_tok = k[:, :, 0], v[:, :, 0]            # [n, KH, D]
        if stage is not None:
            ks, vs = stage
            k_row, v_row = k_tok.astype(ks.dtype), v_tok.astype(vs.dtype)
            if stage_live is not None:
                # Pipeline warmup/cooldown ticks compute garbage rows
                # (pp_model): a guarded write keeps the round's REAL
                # staged K/V intact for the dispatch-boundary commit.
                k_row = jnp.where(stage_live, k_row, ks[l, :, :, stage_step])
                v_row = jnp.where(stage_live, v_row, vs[l, :, :, stage_step])
            ks = ks.at[l, :, :, stage_step].set(k_row)
            vs = vs.at[l, :, :, stage_step].set(v_row)
            attn = paged_decode_attention(
                qg, kf, vf, block_tables, pos,
                page_size=page_size, live_pages=live_pages, layer=l,
                k_stage=ks, v_stage=vs, stage_idx=stage_step,
                mesh=attn_mesh)
            stage = (ks, vs)
        else:
            attn = paged_decode_attention(
                qg, kf, vf, block_tables, pos, k_tok, v_tok,
                page_size=page_size, live_pages=live_pages, layer=l,
                mesh=attn_mesh)
            # Commit AFTER the read-only kernel: same per-step scatter
            # cost as the dense path, in place on the donated pool.
            kf = kf.at[l, write_idx, :, offset, :].set(k_tok)
            vf = vf.at[l, write_idx, :, offset, :].set(v_tok)
        attn = attn.reshape(n, 1, kh * g * c.head_dim)
    else:
        # Write each slot's new K/V at (its current page, offset), then
        # attend over the gathered context [0, pos]. Distinct slots own
        # distinct pages (trash pages for inactive slots), so the
        # scatter has no conflicting indices.
        kf = kf.at[l, write_idx, :, offset, :].set(k[:, :, 0])
        vf = vf.at[l, write_idx, :, offset, :].set(v[:, :, 0])
        if live_pages is not None and live_pages < block_tables.shape[1]:
            block_tables = block_tables[:, :live_pages]
        max_ctx = block_tables.shape[1] * page_size
        live = jnp.arange(max_ctx)[None] <= pos[:, None]   # [n, ctx]
        ck = _gather_ctx(kf, l, block_tables)          # [n, KH, ctx, D]
        cv = _gather_ctx(vf, l, block_tables)
        scores = jnp.einsum("nkgd,nktd->nkgt", qg, ck).astype(jnp.float32)
        scores *= c.head_dim ** -0.5
        scores = jnp.where(live[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
        attn = jnp.einsum("nkgt,nktd->nkgd", probs, cv).reshape(
            n, 1, kh * g * c.head_dim)
    # reshape(-1, hidden): wo's head axis may be a LOCAL tp shard.
    out = jnp.einsum("bsf,fe->bse", attn, layer["wo"].reshape(-1, c.hidden))
    if lora is not None:
        from .lora import lora_delta

        out = out + lora_delta(attn, lora["wo.A"], lora["wo.B"],
                               l, lora_idx).astype(out.dtype)
    if tp_axis is not None:
        # Row-parallel wo: each shard's local-head contribution is a
        # partial sum over the (sharded) head axis.
        out = lax.psum(out, tp_axis)
    return _mlp(x + out, layer, c, tp_axis=tp_axis), kf, vf, stage


def _decode_logits(params, pages: dict, block_tables, tokens, pos,
                   config: LlamaConfig, page_size: int, write_page_idx=None,
                   paged: bool = False, live_pages: int | None = None,
                   lora=None, lora_idx=None, stage=None, stage_step=None,
                   attn_mesh=None):
    """One batched decode step over all slots.

    block_tables: [slots, max_pages_per_seq] int32 (inactive slots must
                  point at their private trash page).
    tokens:       [slots] int32 — token at ``pos[i]`` of each sequence.
    pos:          [slots] int32 — write/attend position.
    write_page_idx: optional [slots] override of the page each slot writes
                  to (the multi-step loop redirects finished slots to
                  their trash page).
    stage/stage_step: paged-v2 staging carry — see ``decode_block``. With
                  staging, the pool comes back UNTOUCHED and the fresh
                  K/V rides the returned stage buffers; the caller owns
                  the dispatch-boundary commit (``commit_staging``).
    Returns (logits [slots, vocab] f32, new pages, stage).
    """
    c = config
    x = params["embed"][tokens][:, None].astype(c.dtype)   # [slots, 1, E]
    if write_page_idx is None:
        write_page_idx = jnp.take_along_axis(
            block_tables, (pos // page_size)[:, None], axis=1)[:, 0]  # [slots]
    page_idx = write_page_idx

    def body(carry, xs):
        x, kf, vf, stg = carry
        layer, l = xs
        x2, kf, vf, stg = decode_block(
            x, layer, kf, vf, l, block_tables, pos, page_idx, c, page_size,
            paged=paged, live_pages=live_pages, lora=lora, lora_idx=lora_idx,
            stage=stg, stage_step=stage_step, attn_mesh=attn_mesh)
        return (x2, kf, vf, stg), None

    (x, new_k, new_v, stage), _ = lax.scan(
        body, (x, pages["k"], pages["v"], stage),
        (params["layers"], jnp.arange(c.n_layers)))
    hidden = rms_norm(x, params["final_norm"], eps=c.norm_eps)     # [slots, 1, E]
    logits = jnp.einsum("bse,ev->bsv", hidden, params["lm_head"])[:, 0]
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}, stage


def commit_staging(pages: dict, stage, write_idx_steps, pos0, n_steps: int,
                   page_size: int):
    """Dispatch-boundary commit: ONE batched scatter folds the staging
    buffer back into the (donated, read-only-until-now) page pool.

    stage:           (k_stage, v_stage) [L, slots, KH, SC, D] — row j of
                     slot s holds the roped K/V of position pos0_s + j.
    write_idx_steps: [n_steps, slots] int32 — the page each slot wrote at
                     each fused step (trash pages for finished slots),
                     recorded by the decode scan.
    pos0:            [slots] int32 — each slot's position at dispatch
                     start (the pool held [0, pos0) throughout).

    By the time this scatter runs the scan that READ the pool has
    completed, so XLA updates the donated buffer in place — the whole
    point of the v2 design: zero pool-sized copies per dispatch.
    """
    k_stage, v_stage = stage
    L, n, kh, _, d = k_stage.shape
    steps = jnp.arange(n_steps, dtype=jnp.int32)
    off = ((pos0[None, :] + steps[:, None]) % page_size).reshape(-1)  # [K*S]
    widx = write_idx_steps.reshape(-1)                                # [K*S]

    def rows(s):
        # [L, S, KH, SC, D] -> staged rows [K*S, L, KH, D] in (step, slot)
        # order matching ``widx``/``off``.
        r = jnp.transpose(s[:, :, :, :n_steps], (3, 1, 0, 2, 4))
        return r.reshape(n_steps * n, L, kh, d)

    new_k = pages["k"].at[:, widx, :, off, :].set(
        rows(k_stage).astype(pages["k"].dtype))
    new_v = pages["v"].at[:, widx, :, off, :].set(
        rows(v_stage).astype(pages["v"].dtype))
    return {"k": new_k, "v": new_v}


@functools.partial(jax.jit, donate_argnames=("pages",))
def copy_pages(pages: dict, src, dst):
    """Copy-on-write fork: duplicate pages ``src`` into pages ``dst``
    across every layer (one gather + one scatter on the donated pool —
    page-granular, never pool-sized). The engine calls this when a slot
    is about to WRITE into a shared prefix page: the fork gets the
    shared page's rows, the slot's table swaps to the fork, and the
    shared original stays immutable for its other readers.

    src/dst: [m] int32 page ids (m is tiny — usually 1).
    """
    return {"k": pages["k"].at[:, dst].set(pages["k"][:, src]),
            "v": pages["v"].at[:, dst].set(pages["v"][:, src])}


@functools.partial(jax.jit, donate_argnames=("pages",))
def write_pages(pages: dict, dst, k_rows, v_rows):
    """KV-migration import: scatter transferred page contents into pages
    ``dst`` across every layer — the inverse of the export gather, and
    the device half of ``import_pages``. Like ``copy_pages`` this is
    page-granular on the donated pool (one scatter, never pool-sized),
    and the destination pages are freshly reserved by the allocator, so
    the write can never alias a live sequence's pages.

    dst: [m] int32 page ids; k_rows/v_rows: [L, m, KH, page, D] host
    arrays (the wire format of a migration chunk).
    """
    return {"k": pages["k"].at[:, dst].set(k_rows.astype(pages["k"].dtype)),
            "v": pages["v"].at[:, dst].set(v_rows.astype(pages["v"].dtype))}


@functools.wraps(_decode_logits)
def _decode_step(*args, **kwargs):
    logits, pages, _ = _decode_logits(*args, **kwargs)
    return logits, pages


decode_step = functools.partial(
    jax.jit,
    static_argnames=("config", "page_size", "paged", "live_pages",
                     "attn_mesh"),
    donate_argnames=("pages",)
)(_decode_step)


@functools.partial(
    jax.jit,
    static_argnames=("config", "page_size", "paged", "live_pages",
                     "attn_mesh"),
    donate_argnames=("pages",))
def decode_and_sample(params, pages: dict, block_tables, tokens, pos, temps, key,
                      config: LlamaConfig, page_size: int, paged: bool = False,
                      live_pages: int | None = None, lora=None, lora_idx=None,
                      attn_mesh=None):
    """``decode_step`` + on-device sampling in ONE compiled program.

    The engine drives the chip through a (possibly remote) dispatch
    channel where every op launch and transfer costs real latency; doing
    argmax/categorical host-side meant ~6 dispatches and a [slots, vocab]
    f32 logits pull PER TOKEN. Here sampling (greedy for temp<=0,
    tempered categorical otherwise) and the RNG split happen on device —
    one dispatch, and only [slots] int32 tokens cross back.
    """
    logits, new_pages, _ = _decode_logits(params, pages, block_tables, tokens,
                                          pos, config, page_size, paged=paged,
                                          live_pages=live_pages, lora=lora,
                                          lora_idx=lora_idx,
                                          attn_mesh=attn_mesh)
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(sub, logits / jnp.maximum(temps, 1e-6)[:, None])
    out = jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)
    return out, key, new_pages


@functools.partial(
    jax.jit,
    static_argnames=("config", "page_size", "n_steps", "paged", "live_pages",
                     "prefill_live_pages", "attn_mesh"),
    donate_argnames=("pages",))
def mixed_dispatch(params, pages: dict, prefill_ops, block_tables, tokens,
                   pos, temps, eos_ids, remaining, key, config: LlamaConfig,
                   page_size: int, n_steps: int, paged: bool = False,
                   live_pages: int | None = None,
                   prefill_live_pages: tuple = (),
                   lora=None, lora_idx=None, attn_mesh=None):
    """Token-budget mixed step: prefill chunk(s) AND the full-batch decode
    burst in ONE compiled program / ONE dispatch (Sarathi-style
    chunked-prefill scheduling: prefill rides along with decode instead of
    preempting it, so a long prompt can no longer head-of-line-block the
    running streams' inter-token latency).

    prefill_ops: static-length tuple of ``(block_table [max_pages],
        tokens [C_i], start_pos)`` — one page-aligned chunk per admitted
        prompt, each ``C_i`` a legacy chunk bucket so this program adds NO
        new prefill shapes, only combinations (the compile key is the
        tuple of bucket sizes × the decode ``live_pages`` bucket).
    prefill_live_pages: per-op static context bound (same bucketing as the
        standalone prefill path).

    The pool interaction is safe by construction: the prefilling
    sequences own disjoint pages from every decoding slot (the allocator
    hands out distinct pages; inactive slots write to private trash
    pages), so chunk scatters and the decode schedule never alias. On the
    paged path the decode scan still only READS the pool — the chunk
    scatters happen before it and ``commit_staging`` after, preserving
    the v2 no-pool-copies property.

    Returns ``(decode_tokens [n_steps, slots], key, pages,
    hiddens tuple)`` — one ``[C_i, E]`` hidden per prefill op, for
    first-token sampling of ops that finished their prompt.
    """
    # the scopes name the device ops of the fused program after its two
    # halves (op_name on the profiler's op line), so that its device time
    # can be split into prefill and decode; they are metadata only
    hiddens = []
    for (p_bt, p_tokens, p_start), lp in zip(prefill_ops, prefill_live_pages):
        with device_scope("prefill_chunk"):
            pages, hidden = prefill_chunk.__wrapped__(
                params, pages, p_bt, p_tokens, p_start,
                config=config, page_size=page_size, live_pages=lp)
        hiddens.append(hidden)
    with device_scope("decode_step"):
        toks, key, pages = decode_loop.__wrapped__(
            params, pages, block_tables, tokens, pos, temps, eos_ids, remaining,
            key, config=config, page_size=page_size, n_steps=n_steps, paged=paged,
            live_pages=live_pages, lora=lora, lora_idx=lora_idx,
            attn_mesh=attn_mesh)
    return toks, key, pages, tuple(hiddens)


@jax.jit
def sample_first_token(last_hidden, lm_head, temp, key):
    """First-token sampling after prefill, on device (one dispatch)."""
    logits = (last_hidden @ lm_head).astype(jnp.float32)
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits)
    sampled = jax.random.categorical(sub, logits / jnp.maximum(temp, 1e-6))
    return jnp.where(temp > 0.0, sampled, greedy).astype(jnp.int32), key


@jax.jit
def sample_first_batch(hiddens, lm_head, temps, key):
    """Batched first-token sampling for several just-prefilled requests
    in ONE dispatch (the engine stacks pending prefills so a burst of
    arrivals costs one host sync total, not one per request).

    hiddens: [m, E] last-position hidden states (padded rows ignored).
    Returns (tokens [m] int32, key).
    """
    logits = (hiddens @ lm_head).astype(jnp.float32)   # [m, vocab]
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(sub, logits / jnp.maximum(temps, 1e-6)[:, None])
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32), key


@functools.partial(
    jax.jit,
    static_argnames=("config", "page_size", "n_steps", "paged", "live_pages",
                     "attn_mesh"),
    donate_argnames=("pages",))
def decode_loop(params, pages: dict, block_tables, tokens, pos, temps, eos_ids,
                remaining, key, config: LlamaConfig, page_size: int, n_steps: int,
                paged: bool = False, live_pages: int | None = None,
                lora=None, lora_idx=None, attn_mesh=None):
    """``n_steps`` decode+sample iterations in ONE dispatch (on-device
    ``lax.scan`` generate loop, JetStream-style).

    Per-token host syncs cost a full dispatch round trip. Scanning K
    steps on device amortizes that to one sync per K tokens. Slots whose
    sequence finishes mid-scan (EOS hit, or ``remaining`` steps
    exhausted) keep computing branchlessly but redirect their KV writes
    to their private trash page, so they can never overrun their page
    allocation or corrupt shared prefix pages; the host discards their
    surplus tokens.

    ``paged=True`` runs the v2 staging-buffer schedule: the pool is
    STRICTLY READ-ONLY across all ``n_steps`` (nothing for XLA to copy
    around the opaque kernel), step ``j`` appends its fresh K/V to a
    small ``[L, slots, KH, SC, D]`` staging carry the kernel folds into
    its online softmax, and ``commit_staging`` writes everything back
    with ONE batched scatter after the scan.

    eos_ids:   [slots] int32 (-1 = no EOS for that slot).
    remaining: [slots] int32 — tokens the slot may still emit (bounds
               both max_new_tokens and the page allocation).
    live_pages: static bound on the attention width — for the dense path
               it must cover ``max(pos) + n_steps - 1`` (tokens land in
               the pool mid-dispatch); for paged it need only cover the
               POOL context ``max(pos)`` (fresher tokens ride staging).
    Returns (tokens [n_steps, slots] int32, key, pages).
    """
    n = tokens.shape[0]
    trash = jnp.arange(n, dtype=jnp.int32)  # slot i's trash page is page i
    stage0 = None
    if paged:
        sc = stage_rows(n_steps)
        shape = (config.n_layers, n, config.n_kv_heads, sc, config.head_dim)
        stage0 = (jnp.zeros(shape, pages["k"].dtype),
                  jnp.zeros(shape, pages["v"].dtype))

    def body(carry, j):
        tokens, cur, done, remaining, key, pages, stage = carry
        real_page = jnp.take_along_axis(
            block_tables,
            jnp.minimum(cur // page_size, block_tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        write_idx = jnp.where(done, trash, real_page)
        logits, pages, stage = _decode_logits(
            params, pages, block_tables, tokens, cur, config, page_size,
            write_page_idx=write_idx, paged=paged, live_pages=live_pages,
            lora=lora, lora_idx=lora_idx, stage=stage,
            stage_step=j if paged else None, attn_mesh=attn_mesh)
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(sub, logits / jnp.maximum(temps, 1e-6)[:, None])
        new_tok = jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)
        remaining = remaining - jnp.where(done, 0, 1)
        done = done | (new_tok == eos_ids) | (remaining <= 0)
        return ((new_tok, cur + 1, done, remaining, key, pages, stage),
                (new_tok, write_idx))

    init = (tokens, pos, remaining <= 0, remaining, key, pages, stage0)
    ((_, _, _, _, key, pages, stage), (toks, widx)) = lax.scan(
        body, init, jnp.arange(n_steps, dtype=jnp.int32))
    if paged:
        # The one pool write of the whole dispatch — the scan above only
        # READ the pool, so the donated buffer updates in place here.
        pages = commit_staging(pages, stage, widx, pos, n_steps, page_size)
    return toks, key, pages


@functools.partial(
    jax.jit,
    static_argnames=("config", "page_size", "n_draft", "paged", "live_pages",
                     "attn_mesh"),
    donate_argnames=("pages",))
def verify_block(params, pages: dict, block_tables, tokens_mat, pos, temps,
                 eos_ids, remaining, key, config: LlamaConfig,
                 page_size: int, n_draft: int, paged: bool = False,
                 live_pages: int | None = None, attn_mesh=None):
    """Speculative verify: score all ``n_draft + 1`` positions of every
    slot's drafted continuation in ONE dispatch — the ``decode_and_sample``
    sibling the speculation stage rides.

    tokens_mat: [slots, S] int32, S = n_draft + 1 — column 0 is each
                slot's current token (the one plain decode would feed at
                ``pos``), columns 1..K its drafted continuation; -1 pads
                a short draft (auto-rejected, never emitted).
    pos:        [slots] int32 — the pool holds K/V for [0, pos) per slot
                (identical precondition to a plain decode step).

    The forward is a tiny batched prefill chunk: every slot's S tokens
    attend over its POOL context [0, pos) plus themselves (causal), so
    one model pass produces the target logits at all S positions. The
    chunk's K/V never touches the pool mid-pass — it accumulates in the
    v2 STAGING carry (the decode_loop machinery, [L, slots, KH, SC, D]),
    the paged kernel folds staged rows [0, j] as position j's second KV
    source, and the dense path masks the pool gather strictly below
    ``pos``. Acceptance then runs on device:

      * greedy (temp <= 0): position j's output is ``argmax(p_j)``;
        draft j+1 is accepted iff it EQUALS that argmax — so every
        emitted token is the argmax the plain decode path would have
        produced, byte for byte.
      * temp > 0: speculative REJECTION sampling — draft d is accepted
        with probability ``p_j(d)`` (the one-hot-proposal case of
        min(1, p/q)); on rejection the emission resamples from the
        residual ``norm(p_j - onehot(d))``, and the position after the
        last accepted draft samples from ``p_j`` directly. The emitted
        distribution is exactly the target's (Leviathan et al. 2023).

    ``live[j, s]`` marks step j of slot s emitted: live_0 = remaining>0,
    live_{j+1} = live_j & accept & no-EOS & within ``remaining``. The
    dispatch-boundary ``commit_staging`` scatter redirects every
    NON-live row to the slot's private trash page — a rejected branch
    (or pad row) never dirties pool pages, so rollback is free and
    shared/COW prefix pages stay byte-stable for their other readers.
    A slot that accepts 0 drafts still emits position 0's token: one
    verify never yields fewer tokens per slot than one decode step.

    Returns ``(tokens [S, slots] int32, live [S, slots] bool, key,
    pages)``.
    """
    c = config
    n, S = tokens_mat.shape
    assert S == n_draft + 1
    kh, g = c.n_kv_heads, c.n_heads // c.n_kv_heads
    steps = jnp.arange(S, dtype=jnp.int32)
    positions = pos[:, None] + steps[None, :]              # [n, S]
    x0 = params["embed"][jnp.maximum(tokens_mat, 0)].astype(c.dtype)
    sc = stage_rows(S)
    stage_shape = (c.n_layers, n, kh, sc, c.head_dim)
    ks0 = jnp.zeros(stage_shape, pages["k"].dtype)
    vs0 = jnp.zeros(stage_shape, pages["v"].dtype)
    gather_tables = block_tables
    if not paged and live_pages is not None \
            and live_pages < block_tables.shape[1]:
        gather_tables = block_tables[:, :live_pages]
    max_ctx = gather_tables.shape[1] * page_size
    ctx_live = jnp.arange(max_ctx)[None, :] < pos[:, None]   # [n, ctx]
    causal = steps[:, None] >= steps[None, :]                # [S, S]

    def body(carry, xs):
        x, kf, vf, ks, vs = carry
        layer, l = xs
        h = rms_norm(x, layer["attn_norm"], eps=c.norm_eps)
        q, k, v = _project_qkv(h, layer)                # [n, H|KH, S, D]
        q = apply_rope(q, positions, theta=c.rope_theta)
        k = apply_rope(k, positions, theta=c.rope_theta)
        # Stage ALL S rows (accept/reject is decided after the forward);
        # the commit scatter, not the stage, is what gates the pool.
        ks = ks.at[l, :, :, :S, :].set(k.astype(ks.dtype))
        vs = vs.at[l, :, :, :S, :].set(v.astype(vs.dtype))
        qg = q.reshape(n, kh, g, S, c.head_dim)
        if paged:
            # One kernel call per chunk position: position j folds
            # staged rows [0, j] (its own causal prefix) on top of the
            # pool pages — the exact schedule decode_loop's step j uses,
            # so paged verify logits match paged decode bit for bit.
            outs = []
            for j in range(S):
                outs.append(paged_decode_attention(
                    qg[:, :, :, j], kf, vf, block_tables, pos + j,
                    page_size=page_size, live_pages=live_pages, layer=l,
                    k_stage=ks, v_stage=vs, stage_idx=j, mesh=attn_mesh))
            attn = jnp.stack(outs, axis=3)              # [n, KH, G, S, D]
        else:
            ck = _gather_ctx(kf, l, gather_tables)      # [n, KH, ctx, D]
            cv = _gather_ctx(vf, l, gather_tables)
            scale = c.head_dim ** -0.5
            s_ctx = jnp.einsum("nkgsd,nktd->nkgst", qg, ck
                               ).astype(jnp.float32)
            s_self = jnp.einsum("nkgsd,nktd->nkgst", qg, k
                                ).astype(jnp.float32)
            s_ctx = jnp.where(ctx_live[:, None, None, None],
                              s_ctx * scale, -jnp.inf)
            s_self = jnp.where(causal[None, None, None],
                               s_self * scale, -jnp.inf)
            probs = jax.nn.softmax(
                jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1)
            p_ctx = probs[..., :max_ctx].astype(c.dtype)
            p_self = probs[..., max_ctx:].astype(c.dtype)
            attn = jnp.einsum("nkgst,nktd->nkgsd", p_ctx, cv) + \
                jnp.einsum("nkgst,nktd->nkgsd", p_self, v)
        attn = attn.reshape(n, c.n_heads, S, c.head_dim)
        flat = jnp.swapaxes(attn, 1, 2).reshape(n, S, -1)
        out = jnp.einsum("nsf,fe->nse", flat,
                         layer["wo"].reshape(c.n_heads * c.head_dim,
                                             c.hidden))
        return (_mlp(x + out, layer, c), kf, vf, ks, vs), None

    (x, kf, vf, ks, vs), _ = lax.scan(
        body, (x0, pages["k"], pages["v"], ks0, vs0),
        (params["layers"], jnp.arange(c.n_layers)))
    hidden = rms_norm(x, params["final_norm"], eps=c.norm_eps)  # [n, S, E]
    logits = jnp.einsum("nse,ev->nsv", hidden,
                        params["lm_head"]).astype(jnp.float32)

    # ----- acceptance + emission, all on device (one sync total) -----
    vocab = logits.shape[-1]
    # Draft considered AT step j is tokens_mat[:, j + 1]; the last step
    # has none (-1) — its emission is the bonus/fresh sample.
    d_ext = jnp.concatenate(
        [tokens_mat[:, 1:], jnp.full((n, 1), -1, jnp.int32)], axis=1)
    valid = d_ext >= 0
    d_clip = jnp.maximum(d_ext, 0)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [n, S]
    p = jax.nn.softmax(
        logits / jnp.maximum(temps, 1e-6)[:, None, None], axis=-1)
    p_draft = jnp.take_along_axis(p, d_clip[..., None], axis=-1)[..., 0]
    key, ku, kr = jax.random.split(key, 3)
    u = jax.random.uniform(ku, p_draft.shape)
    accept_sampled = valid & (u < p_draft)
    # Residual distribution norm(max(p - q, 0)) for a one-hot proposal:
    # zero the draft index, renormalize (categorical normalizes).
    padj = p * (1.0 - jax.nn.one_hot(d_clip, vocab, dtype=p.dtype)
                * valid[..., None].astype(p.dtype))
    resample = jax.random.categorical(
        kr, jnp.log(padj + 1e-30)).astype(jnp.int32)
    o_sampled = jnp.where(accept_sampled, d_clip, resample)
    sampled_on = (temps > 0.0)[:, None]
    o = jnp.where(sampled_on, o_sampled, greedy).astype(jnp.int32)
    accept = jnp.where(sampled_on, accept_sampled,
                       valid & (greedy == d_clip))
    cont = accept & (o != eos_ids[:, None]) \
        & (remaining[:, None] > steps[None, :] + 1)
    live = jnp.concatenate(
        [jnp.ones((n, 1), bool),
         jnp.cumprod(cont[:, :-1].astype(jnp.int32), axis=1).astype(bool)],
        axis=1) & (remaining > 0)[:, None]                   # [n, S]

    # Dispatch-boundary commit: live rows land at their real (page,
    # offset); rejected/pad rows go to the slot's trash page — the pool
    # only ever sees ACCEPTED K/V, so a rolled-back branch is free.
    page_of = jnp.take_along_axis(
        block_tables,
        jnp.minimum(positions // page_size, block_tables.shape[1] - 1),
        axis=1)
    trash = jnp.arange(n, dtype=jnp.int32)
    widx = jnp.where(live, page_of, trash[:, None])          # [n, S]
    pages = commit_staging({"k": kf, "v": vf}, (ks, vs),
                           jnp.swapaxes(widx, 0, 1), pos, S, page_size)
    return jnp.swapaxes(o, 0, 1), jnp.swapaxes(live, 0, 1), key, pages
