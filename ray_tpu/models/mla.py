"""Latent attention (MLA; DeepSeek-V2, arXiv 2405.04434) as three token
mixers, as ``models/gdn.py`` is one, told apart by their choice of keys:
``mla``, whose keys a learned indexer chooses (DeepSeek-V3.2's sparse
attention), ``mla_win``, a causal window, and ``mla_full``, every causal key
(DeepSeek-V3, Kimi-K2). Each is described by a ``LatentAttention`` on the
config (``mla``, ``mla_window``, ``mla_full``) and they share every line but
that choice.

For the normed input ``x`` of a position, ranks r_q and r_kv, H heads of d_n
(no position) + d_r (rope) query and key features and d_v value features:

    c_q          = s_q rmsnorm(x W_dq)                      [r_q]
    q_h          = c_q W_uq[h],  rope on its last d_r       [d_n + d_r]
    [c_kv | k_r] = x W_dkv;  c_kv = s_kv rmsnorm(c_kv);  k_r = rope(k_r)
    [k_h^n | v_h] = c_kv W_ukv[h];  k_h = [k_h^n | k_r]     one k_r for all heads
    o_h          = softmax over the allowed keys (f q_h . k_h (d_n + d_r)^-1/2) v_h
    y            = concat_h(sigmoid(x W_g)_h o_h) W_o       (``gate``)

``s_q = (hidden / r_q)^1/2``, ``s_kv = (hidden / r_kv)^1/2`` with ``rescale``,
else 1. Rope turns by ``rope_theta``'s plain frequencies, or with ``yarn`` (a
``LatentAttentionYarn``) by YaRN's blended ones (``ops/rope.py``). YaRN has
two published forms, and such a spec can state either: ``models/gqa.py``'s
puts its factor on cos and sin (``yarn.attention_factor``); latent attention's
leaves cos and sin alone (their factor is ``mscale(F, mscale) / mscale(F,
mscale_all_dim)``, 1 where the two keys are equal) and multiplies the SOFTMAX
SCALE by ``f = softmax_factor = mscale(F, mscale_all_dim)^2``, ``mscale(F, m)
= 0.1 m ln F + 1``; where the configuration is built computes both from the
published keys.
Allowed keys of query t: ``mla_full``: every s <= t; ``mla_win``: s in
[t - window + 1, t]; ``mla``: the ``index_top_k`` keys the indexer scores
highest (``ops/sparse_index.py``), from

    q^I_j = c_q W_iq[j]  (rope on its first d_r),  k^I = rope(layernorm(x W_ik)),
    w^I   = x W_iw J^-1/2 Di^-1/2

Training the indexer, as its publication does: the model's loss reaches no
indexer leaf (a top-k passes no gradient), the indexer's inputs ``x`` and
``c_q`` are cut from the graph, and the mixer returns beside ``y`` the
indexer's own loss ``index_loss`` (the KL from attention's head-summed
probabilities over the chosen keys, a constant, to the softmax of the index
scores over them) and ``selected_share`` (keys attended over causal keys).

``SAVE_NAMES`` is what a backward pass reads and cannot cheaply remake: the
latents and not the per-head q, k and v made from them (3,712 numbers a
token against 65,536 in a full layer), the kernel's output and logsumexp,
the gate, the key sets (int8, 67 MB a row of 8k), the two statistics a query
that the indexer's loss hands its backward kernel (``dsa_kl_z``, ``dsa_kl_lse``,
named inside ``index_kl``'s rule: saved, the loss's forward kernel runs once)
and ``kinds.POST_ATTN``, the residual stream as the mixer's output joins it
(the stack's name: ``models/llama.py::_block`` gives it in every kind's
block, and a kind that does not list it saves nothing by it). No gradient
reads the mixer's output:
the block's second run needs it only to remake that stream for the MLP's
norm, and making it again is ``wo``, the layer's widest product. By FLOPs of
second run a saved byte the stream is the cheapest thing in the block to keep:
heads x v_dim = 16,384 (128 heads) or 8,192 (64) for a byte of ``[B, S,
hidden]`` bf16, against 2 x rank = 1,024-2,048 for q, k and v, which stay
remade. The SUM and not the mixer's output: the chip's compiler adds ``wo``'s
float32 product to the stream inside one fusion and rounds once; a saved bf16
output is rounded before the add, in the forward pass too, and the sparse
cell's step then stands 1.6 times as far from its float32 reference (PERF.md,
PR 52). Saved, the stream is rounded where it is made (``jax.checkpoint`` puts
a ``reduce_precision`` on a residual's producer) and the MLP norm's mean square
reads the rounded values, where a program that is not differentiated reads the
float32 sum: the step and a forward-only program on the same batch then choose
the next indexed layer's keys alike to 0.9960 and not 0.99991 (PERF.md, PR 52:
what a comparison that hands a reference the second program's sets sees).
``mla`` and ``mla_win`` save it (the sparse cell's step, 2 x 8192:
11.72 -> 11.96 GB by the chip compiler's count). ``mla_full`` does not (it
keeps ``LATENT_NAMES``, the mixer's own): Kimi-K2's step at 4,096 tokens
stands at 16.27 GB of the chip's 16.91 and reads 16.80 GB with five more
arrays of 59 MB kept, past the 16.60e9 bytes ISSUE 52 set before the count.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import apply_rope, flash_attention, rms_norm
from ..ops.sparse_index import index_kl, index_scores, select_top_k
from .kinds import POST_ATTN, LayerKind, Yarn, sigmoid_gate, kept_keys, rope_keywords

LATENT_NAMES = ("mla_cq", "mla_ckv", "mla_kr", "attn_out", "attn_lse", "attn_gate",
                "dsa_mask", "dsa_kl_z", "dsa_kl_lse")  # what ``mla_mixer`` itself names
SAVE_NAMES = LATENT_NAMES + (POST_ATTN,)  # and the stream, where the step has the room
INDEX_NORM_EPS = 1e-6  # the index key's LayerNorm


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The widths of one kind of latent-attention layer."""

    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    window: int = 0          # 0: none. Else query t sees keys t - window + 1 .. t
    index_heads: int = 0     # 0: no indexer. With no window either: every causal key
    index_dim: int = 0
    index_top_k: int = 0
    rescale: bool = False
    gate: bool = False
    # what a spec under YaRN states (``LatentAttentionYarn``'s fields); here
    # facts of the class: plain frequencies, the plain softmax scale
    yarn = None
    softmax_factor = 1.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


@dataclasses.dataclass(frozen=True)
class LatentAttentionYarn(LatentAttention):
    """The widths of a latent-attention layer under YaRN in the form latent
    attention publishes (a spec of its own: the benchmark's accepted tests
    hold ``LatentAttention`` to its thirteen fields)."""

    yarn: Yarn | None = None        # YaRN's frequencies, its factor on cos and sin
    softmax_factor: float = 1.0     # times the softmax scale (YaRN's mscale^2)


def _axes(a: LatentAttention) -> dict:
    axes = {
        "w_dq": ("embed", None), "q_a_norm": ("norm",),
        "w_uq": (None, "heads", "head_dim"),
        "w_dkv": ("embed", None), "kv_a_norm": ("norm",),
        "w_ukv": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if a.gate:
        axes["w_attn_gate"] = ("embed", "heads")
    if a.index_heads:
        axes.update(w_iq=(None, None, None), w_ik=("embed", None), ik_norm=("norm",),
                    ik_bias=("norm",), w_iw=("embed", None))
    return axes


def _init(a: LatentAttention, c, keys, lead, normal) -> dict:
    e, h = c.hidden, a.heads
    k_q, k_kv, k_o, k_rest = keys
    k_dq, k_uq = jax.random.split(k_q)
    k_dkv, k_ukv = jax.random.split(k_kv)
    k_g, k_iq, k_ik, k_iw = jax.random.split(k_rest, 4)
    params = {
        "w_dq": normal(k_dq, lead + (e, a.q_rank), e),
        "q_a_norm": jnp.ones(lead + (a.q_rank,), c.dtype),
        "w_uq": normal(k_uq, lead + (a.q_rank, h, a.qk_dim), a.q_rank),
        "w_dkv": normal(k_dkv, lead + (e, a.kv_rank + a.rope_dim), e),
        "kv_a_norm": jnp.ones(lead + (a.kv_rank,), c.dtype),
        "w_ukv": normal(k_ukv, lead + (a.kv_rank, h, a.nope_dim + a.v_dim), a.kv_rank),
        "wo": normal(k_o, lead + (h, a.v_dim, e), h * a.v_dim),
    }
    if a.gate:
        params["w_attn_gate"] = normal(k_g, lead + (e, h), e)
    if a.index_heads:
        params.update(
            w_iq=normal(k_iq, lead + (a.q_rank, a.index_heads, a.index_dim), a.q_rank),
            w_ik=normal(k_ik, lead + (e, a.index_dim), e),
            ik_norm=jnp.ones(lead + (a.index_dim,), c.dtype),
            ik_bias=jnp.zeros(lead + (a.index_dim,), c.dtype),
            w_iw=normal(k_iw, lead + (e, a.index_heads), e))
    return params


def _layer_norm(x, weight, bias, eps):
    f = x.astype(jnp.float32)
    f = f - jnp.mean(f, axis=-1, keepdims=True)
    f = f * jax.lax.rsqrt(jnp.mean(f * f, axis=-1, keepdims=True) + eps)
    return (f * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _rope(t, positions, a: LatentAttention, rotary_dim: int | None = None):
    """Rope on t [B, H, S, D], whole or on its first ``rotary_dim`` features:
    ``rope_theta``'s frequencies, or YaRN's over the ``rope_dim`` rotated."""
    kw = rope_keywords(a.rope_dim, a.rope_theta, a.yarn)
    if rotary_dim is not None:
        kw["rotary_dim"] = rotary_dim
    return apply_rope(t, positions, **kw)


def _rope_tail(t, positions, a: LatentAttention):
    """Rope on the LAST ``rope_dim`` features of t [B, H, S, D]."""
    split = t.shape[-1] - a.rope_dim
    return jnp.concatenate([t[..., :split], _rope(t[..., split:], positions, a)], axis=-1)


def index_inputs(h, c_q, layer, a: LatentAttention, positions):
    """The indexer's three operands from the mixer's (cut) inputs: q^I
    [B, J, S, Di], k^I [B, S, Di], w^I [B, S, J] float32."""
    h, c_q = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
    q_i = jnp.einsum("bsr,rjd->bjsd", c_q, layer["w_iq"])
    q_i = _rope(q_i, positions, a, a.rope_dim)
    k_i = _layer_norm(jnp.einsum("bse,ed->bsd", h, layer["w_ik"]),
                      layer["ik_norm"], layer["ik_bias"], INDEX_NORM_EPS)
    k_i = _rope(k_i[:, None], positions, a, a.rope_dim)[:, 0]
    w_i = jnp.einsum("bse,ej->bsj", h, layer["w_iw"], preferred_element_type=jnp.float32)
    return q_i, k_i, w_i * (a.index_heads ** -0.5 * a.index_dim ** -0.5)


def mla_mixer(h, layer, a: LatentAttention, *, config, positions, mesh=None,
              return_selection: bool = False):
    """h [B, S, E] (normed) -> (y [B, S, E], aux). ``aux`` is ``{}`` for a
    window or a full layer and ``index_loss``, ``selected_share`` for an indexed one;
    ``return_selection`` adds the key sets [B, S, S] int8 (comparisons)."""
    c = config
    b, s, e = h.shape
    s_q = math.sqrt(e / a.q_rank) if a.rescale else 1.0
    s_kv = math.sqrt(e / a.kv_rank) if a.rescale else 1.0

    def latent(x, weight, scale):
        x = rms_norm(x, weight, eps=c.norm_eps)
        return x if scale == 1.0 else (x.astype(jnp.float32) * scale).astype(x.dtype)

    with device_scope("mla_q"):
        c_q = checkpoint_name(
            latent(jnp.einsum("bse,er->bsr", h, layer["w_dq"]), layer["q_a_norm"], s_q),
            "mla_cq")
        q = _rope_tail(jnp.einsum("bsr,rhd->bhsd", c_q, layer["w_uq"]), positions, a)
    with device_scope("mla_kv"):
        down = jnp.einsum("bse,er->bsr", h, layer["w_dkv"])
        c_kv = checkpoint_name(latent(down[..., :a.kv_rank], layer["kv_a_norm"], s_kv),
                               "mla_ckv")
        k_r = checkpoint_name(_rope(down[:, None, :, a.kv_rank:], positions, a), "mla_kr")
        kv = jnp.einsum("bsr,rhd->bhsd", c_kv, layer["w_ukv"])
        k = jnp.concatenate(
            [kv[..., :a.nope_dim], jnp.broadcast_to(k_r, (b, a.heads, s, a.rope_dim))], axis=-1)
        v = kv[..., a.nope_dim:]
    sm_scale = a.qk_dim ** -0.5 * a.softmax_factor
    aux = {}
    if a.index_heads:
        with device_scope("dsa_index"):
            scores = index_scores(*index_inputs(h, c_q, layer, a, positions))
        with device_scope("dsa_select"):
            mask = checkpoint_name(
                select_top_k(jax.lax.stop_gradient(scores), a.index_top_k), "dsa_mask")
        attn, lse = flash_attention(q, k, v, sm_scale=sm_scale, mask=mask,
                                    top_k=a.index_top_k, return_lse=True)
        with device_scope("dsa_loss"):
            aux["index_loss"] = index_kl(q, k, lse, scores, mask, sm_scale=sm_scale)
            aux["selected_share"] = (jnp.sum(mask, dtype=jnp.float32)
                                     / (b * s * (s + 1) / 2))
        if return_selection:
            aux["selection"] = mask
    elif a.window:
        # blocks of the window's size: a query block's band is two key blocks
        # (as many key heads as query heads: nothing for the kernels to fold)
        attn = flash_attention(q, k, v, sm_scale=sm_scale, window=a.window,
                               block_q=512, block_k=512)
    else:
        # every causal key: the plain kernels at their own blocks
        with device_scope("mla_full"):
            attn = flash_attention(q, k, v, sm_scale=sm_scale)
    if a.gate:
        with device_scope("attn_gate"):
            attn = sigmoid_gate(h, layer["w_attn_gate"], attn)
    with device_scope("mla_out"):
        return jnp.einsum("bhsd,hde->bse", attn, layer["wo"]), aux


def _matmul_params(a: LatentAttention, c) -> float:
    """Matmul parameters a token passes through. The indexer's count two
    thirds: its inputs are cut from the graph, so its backward pass makes the
    weights' gradients and no input's (4 N FLOPs a token where a layer's
    other matrices cost 6 N)."""
    e, h = c.hidden, a.heads
    main = (e * a.q_rank + a.q_rank * h * a.qk_dim + e * (a.kv_rank + a.rope_dim)
            + a.kv_rank * h * (a.nope_dim + a.v_dim) + h * a.v_dim * e
            + (e * h if a.gate else 0))
    index = (a.q_rank * a.index_heads * a.index_dim + e * a.index_dim
             + e * a.index_heads) if a.index_heads else 0
    return main + index * 2.0 / 3.0


def _mixing_flops(a: LatentAttention, c, seq: int) -> float:
    """Forward FLOPs a token that are no parameter product, USEFUL work only:
    scores and values over the keys a query keeps (the window's, the
    selection's, whatever a kernel walks), and the index scores over every
    causal key (all are scored). The head-summed probabilities the
    indexer's target needs are this program's way to the loss and no model
    FLOP, as a recomputed block is none."""
    width = a.window or a.index_top_k or seq
    attention = 2.0 * a.heads * (a.qk_dim + a.v_dim) * kept_keys(seq, width)
    index = 2.0 * a.index_heads * a.index_dim * (seq + 1) / 2
    return attention + index


def _kind(field: str, save_names: tuple) -> LayerKind:
    spec = lambda c: getattr(c, field)  # noqa: E731
    return LayerKind(
        axes=lambda c: _axes(spec(c)),
        init=lambda c, keys, lead, normal: _init(spec(c), c, keys, lead, normal),
        apply=lambda h, layer, **kw: mla_mixer(h, layer, spec(kw["config"]), **kw),
        matmul_params=lambda c: _matmul_params(spec(c), c),
        mixing_flops=lambda c, seq: _mixing_flops(spec(c), c, seq),
        save_names=save_names)


MLA = _kind("mla", SAVE_NAMES)
MLA_WINDOW = _kind("mla_window", SAVE_NAMES)
MLA_FULL = _kind("mla_full", LATENT_NAMES)  # Kimi-K2's step: 16.80 GB of 16.91 with the stream

__all__ = ["LatentAttention", "LatentAttentionYarn", "MLA", "MLA_WINDOW", "MLA_FULL",
           "SAVE_NAMES", "LATENT_NAMES", "mla_mixer",
           "index_inputs", "kept_keys"]
