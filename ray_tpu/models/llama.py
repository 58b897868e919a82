"""A pre-norm decoder described by layer kinds, TPU-first.

A block is ``x += mixer(norm(x)); x += mlp(norm(x))``, the MLP kind first
given the block's input ``x`` itself for what it computes from that alone (a
router that reads the residual stream before the attention norm; the dense
kinds take nothing from it). The stack knows no
more than that: a token mixer (``attn``: softmax attention, below;
``gdn``: Gated DeltaNet, ``models/gdn.py``; ``mla`` / ``mla_win`` / ``mla_full``:
latent attention under a learned selection of keys, a window or over every
causal key, ``models/mla.py``;
``gqa`` / ``gqa_win``: grouped-query attention whose widths a spec owns,
whole or under a window, ``models/gqa.py``; ``lightning``: linear attention
under a constant decay, ``models/lightning.py``; ``block_sparse``: grouped
queries over key blocks the layer selects, ``models/block_sparse.py``;
``mamba2``: a selective state-space layer, ``models/mamba2.py``; ``sconv``:
a double-gated short convolution, ``models/short_conv.py``)
and an MLP (``dense``, below; ``moe``: the routed experts,
``models/moe.py``) are ``LayerKind``s (``models/kinds.py``) that own their
leaves, logical axes, init, FLOPs, counters and the names a remat policy
may save. ``layer_pattern`` lists the mixers of one PERIOD of the stack,
``lead_pattern`` those of the leading layers before the periods, outside
the scan, whose MLP is a dense one of its own width. Llama-3, InternLM2,
Mistral, Mixtral and OLMoE are a period of one attention block; Qwen3-Next
is three DeltaNet blocks and one of gated attention; dots3-note-prev is a
leading dense layer, then an indexed and three window layers; Laguna-S-2.1
a leading dense layer under full attention, then three window layers of 72
heads and a full one of 48; Kimi-K2 a leading dense layer and a period of
one expert layer, all under latent attention over every causal key;
SmallThinker a period of one un-roped full layer and three roped 4,096-key
window layers, no leading layer, ReGLU experts whose router reads the block's
input; MiniCPM-SALA a period of one block-selected layer and three of lightning
attention, under fixed multipliers on the embedding, the residual branches and
the head's input; Granite-4.0-H a period of TEN, nine Mamba-2 state-space
layers around one un-roped attention layer whose softmax scale is a constant,
the embedding tied to the head, under the same three multipliers; LFM2 two
leading dense layers under short convolutions, then a period of one roped
attention layer with per-head q/k norms and three short convolutions, sigmoid
routing under a selection bias with no shared expert, the embedding tied.

Design choices (vs. a torch port):
- Layers are **stacked and scanned** (`lax.scan`) over periods: the body is
  the pattern's blocks in order, the leaves of each position of the period
  stacked over the periods, so one compiled body regardless of depth;
  `jax.checkpoint` on each block trades FLOPs for HBM (rematerialisation).
- Params are a plain pytree of jnp arrays; ``param_axes(config)`` returns a
  matching tree of logical-axis tuples consumed by
  ``ray_tpu.parallel.sharding`` — strategy changes never touch this file.
- Attention is the Pallas flash kernel (``ray_tpu.ops.flash_attention``)
  or ring attention over the ``sp`` mesh axis for long context.
- bf16 params/activations, f32 softmax/norm statistics and loss.

The reference has no model code of its own (models live in torch/vLLM
behind Train/Serve); this supplies the TPU-native equivalent.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ..observability.tracing import device_scope, with_passes
from ..ops import (mha_reference, ring_attention, rms_norm, apply_rope,
                   ulysses_attention)
from ..ops.moe_rows import take_rows
from ..parallel.sharding import shard_constraint
from .block_sparse import BLOCK_SPARSE, BlockSparseAttention
from .gdn import GDN
from .gqa import GQA, GQA_WINDOW, GroupedQueryAttention, ScaledGroupedQueryAttention
from .kinds import POST_ATTN, LayerKind, Yarn, flash_per_shard, norm_over_heads
from .lightning import LIGHTNING, LightningAttention
from .mamba2 import MAMBA2, Mamba2
from .mla import MLA, MLA_FULL, MLA_WINDOW, LatentAttention, LatentAttentionYarn
from .moe import MOE, bias_step
from .short_conv import SCONV


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """One decoder: its widths, the kinds of its layers and the facts of its
    architecture. The name is from when the file held one family; the
    defaults are still llama-3-8b's."""

    vocab_size: int = 128_256
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    intermediate: int = 14_336
    head_dim: int = 128
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # attention implementation: "flash" | "ring" | "reference"
    attn_impl: str = "flash"
    remat: bool = True
    # "full": recompute the whole block in backward (min HBM);
    # "dots": save matmul outputs, recompute elementwise only (XLA
    # checkpoint_policies.dots_with_no_batch_dims_saveable) — trades HBM
    # for ~1 forward less recompute per step.
    remat_policy: str = "full"
    # MoE: when n_experts > 0 the MLP becomes a top-k routed expert layer
    # (models/moe.py: dropless), ``intermediate`` is ONE expert's width and
    # the experts shard over the ``ep`` mesh axis. These are facts of an
    # architecture, not knobs: whether the k chosen gates are renormalised
    # (Mixtral: yes; OLMoE: no) and the weights of the two auxiliary terms
    # (load balance; router z-loss), each the mean over layers.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    moe_aux_weight: float = 0.01
    moe_z_weight: float = 0.0
    # OLMoE: an RMSNorm with a learned weight over the whole q and the whole
    # k projection, before the split into heads and before rope.
    qk_norm: bool = False
    # The token mixers of one period of the stack, in order ("attn" | "gdn");
    # n_layers is a multiple of its length. Qwen3-Next: gdn gdn gdn attn.
    layer_pattern: tuple[str, ...] = ("attn",)
    # Facts of an architecture, as above. Every RMSNorm multiplies by
    # ``1 + w`` and starts ``w`` at zero; q and k are normalised per head,
    # over head_dim, before rope; rope turns only the first ``rotary_dim``
    # features of a head (0: all of them), by ``rope_theta``'s plain
    # frequencies; ``attn_out_gate`` is Qwen3-Next's ELEMENT-wise gate: the q
    # projection is twice as wide, a head's second half is its gate, and
    # attention's output is multiplied by its sigmoid feature by feature. All
    # four are the ``attn`` kind's alone: a head-wise gate (one number a
    # head), YaRN, a window or a second head count belong to a spec
    # (``gqa`` / ``gqa_window``, ``mla`` / ``mla_window`` below).
    norm_plus_one: bool = False
    head_qk_norm: bool = False
    rotary_dim: int = 0
    attn_out_gate: bool = False
    # Gated DeltaNet mixer: key heads, value heads (a multiple), the size of
    # both kinds of head, the causal conv's width.
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_head_dim: int = 128
    gdn_conv: int = 4
    # MoE: width of the always-on shared expert (0: none), and which of the
    # ``moe_experts`` the router scores this program holds, as (first, count)
    # (None: all): one chip's share of an expert-parallel deployment, on the
    # plain jit path, with no exchange (models/moe.py).
    moe_shared: int = 0
    moe_held: tuple[int, int] | None = None
    # MoE, facts of an architecture again. How the router scores: "softmax",
    # or "sigmoid" (DeepSeek-V3's: the k experts are the top-k of score +
    # bias, the gates the chosen scores, and the balance term is taken a
    # sequence at a time); whether the router has that selection bias, a
    # leaf no gradient moves and ``update_buffers`` steps by ``moe_bias_rate``
    # from a step's own counts; whether the shared expert is scaled by a
    # sigmoid of its own (Qwen's is; DeepSeek's is not).
    # ``moe_routed_scale`` multiplies the (normalised) gates of the routed
    # experts, so their sum's weight against the shared expert's
    # (``moe_routed_scaling_factor``; Laguna-S-2.1: 2.5).
    moe_score: str = "softmax"
    moe_bias_rate: float = 0.0
    moe_shared_gate: bool = True
    moe_routed_scale: float = 1.0
    # Which tensor of a token the router reads: "mlp_norm", the expert
    # layer's own normed input (every model above), or "block", the residual
    # stream as it enters the block, before the attention norm
    # (SmallThinker's: the routing then waits for nothing the mixer computes).
    # And the experts' gate: "silu" (SwiGLU) or "relu" (ReGLU, SmallThinker's).
    moe_router_input: str = "mlp_norm"
    moe_activation: str = "silu"
    # Latent attention (models/mla.py): the widths of the mixer kinds "mla"
    # (keys chosen by an indexer), "mla_win" (a causal window) and "mla_full"
    # (every causal key).
    mla: LatentAttention | None = None
    mla_window: LatentAttention | None = None
    mla_full: LatentAttention | None = None
    # Grouped-query attention by spec (models/gqa.py): the widths of the
    # mixer kinds "gqa" (every causal key) and "gqa_win" (a causal window).
    gqa: GroupedQueryAttention | None = None
    gqa_window: GroupedQueryAttention | None = None
    # Leading layers before the periods, outside the scan: their mixers, in
    # order, and the width of their dense MLP (DeepSeek's
    # ``first_k_dense_replace``). ``n_layers`` counts them.
    lead_pattern: tuple[str, ...] = ()
    lead_intermediate: int = 0
    # Lightning attention (models/lightning.py) and block-selected attention
    # (models/block_sparse.py): the widths of the mixer kinds "lightning" and
    # "block_sparse". ``layer_ids``: for a cut of a published stack, the
    # published index of each of this stack's layers, in order (empty: their
    # own), for what a layer computes from its index (lightning's decay).
    lightning: LightningAttention | None = None
    block_sparse: BlockSparseAttention | None = None
    layer_ids: tuple[int, ...] = ()
    # Fixed multipliers (MiniCPM's muP scalings, facts of an architecture):
    # on the embedding's rows (``scale_emb``), on each residual branch before
    # it joins the stream (``scale_depth / sqrt(layers)``), and on the final
    # norm's output before the head (``dim_model_base / hidden``). At 1 the
    # program is what it is without them.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # Mamba-2 state-space layers (models/mamba2.py): the widths of the mixer
    # kind "mamba2". ``tie_embeddings``: the head contracts the embedding's own
    # rows (``tie_word_embeddings``): no ``lm_head`` leaf, and the table's
    # gradient is the sum of the row gather's and the head's.
    mamba2: Mamba2 | None = None
    tie_embeddings: bool = False
    # Double-gated short convolution (models/short_conv.py), the mixer kind
    # "sconv": the taps of its causal depthwise conv (LFM2's ``conv_L_cache``).
    sconv_taps: int = 3
    # Pipeline parallelism: microbatches per step when the mesh has pp > 1.
    pipeline_microbatches: int = 4

    def __post_init__(self):
        if self.moe_router_input not in ("mlp_norm", "block"):
            raise ValueError(f"moe_router_input is {self.moe_router_input!r}: "
                             "'mlp_norm' or 'block'")
        if self.moe_activation not in ("silu", "relu"):
            raise ValueError(f"moe_activation is {self.moe_activation!r}: 'silu' or 'relu'")
        if self.layer_ids and len(self.layer_ids) != self.n_layers:
            raise ValueError(f"{len(self.layer_ids)} layer ids for {self.n_layers} layers")

    @property
    def norm_offset(self) -> float:
        return 1.0 if self.norm_plus_one else 0.0

    @property
    def n_periods(self) -> int:
        scanned = self.n_layers - len(self.lead_pattern)
        if scanned % len(self.layer_pattern):
            raise ValueError(f"{scanned} layers are no whole number of "
                             f"periods of {self.layer_pattern}")
        return scanned // len(self.layer_pattern)


PRESETS: dict[str, LlamaConfig] = {
    # llama-3-8b: the BASELINE.md north-star model
    "llama3-8b": LlamaConfig(),
    "llama3-1b": LlamaConfig(hidden=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                             intermediate=8192, head_dim=64),
    # Exact 8B layer dims (hidden 4096, 32 q-heads, head_dim 128) at 8
    # layers so params+optimizer fit one 16 GB chip: the honest per-layer
    # perf point for the 8B north star (MFU is computed from THIS config).
    "llama3-8b-proxy": LlamaConfig(n_layers=8),
    # tiny configs for tests / dryruns
    "debug": LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, intermediate=128, head_dim=16),
    "debug-128": LlamaConfig(vocab_size=512, hidden=128, n_layers=2, n_heads=4,
                             n_kv_heads=2, intermediate=256, head_dim=32),
    # MoE family (Mixtral-style top-2 routing)
    "llama-moe-debug": LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                                   n_kv_heads=2, intermediate=128, head_dim=16,
                                   moe_experts=4, moe_norm_topk=True),
    "mixtral-8x7b-ish": LlamaConfig(hidden=4096, n_layers=32, n_heads=32,
                                    n_kv_heads=8, intermediate=14_336, head_dim=128,
                                    moe_experts=8, moe_norm_topk=True),
    # a hybrid period for tests: three DeltaNet blocks and one of gated
    # attention (partial rope, 1 + w norms), a shared expert, 2 of 8 experts
    "hybrid-debug": LlamaConfig(vocab_size=256, hidden=64, n_layers=4, n_heads=4,
                                n_kv_heads=2, intermediate=32, head_dim=32,
                                norm_eps=1e-6, layer_pattern=("gdn", "gdn", "gdn", "attn"),
                                norm_plus_one=True, head_qk_norm=True, rotary_dim=8,
                                attn_out_gate=True, gdn_key_heads=2, gdn_value_heads=4,
                                gdn_head_dim=16, moe_experts=8, moe_top_k=3,
                                moe_norm_topk=True, moe_shared=32, moe_held=(0, 2),
                                moe_aux_weight=0.001),
    # latent attention at test size: a leading dense layer under an indexed
    # mixer, then a period of one indexed and three window layers; top-4 of
    # the keys and a window of 5, so both drop keys at 16+ positions; a
    # sigmoid router with its bias, 2 of 8 experts held, a plain shared expert
    "latent-sparse-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=5, n_heads=4, n_kv_heads=4, intermediate=32,
        head_dim=16, layer_pattern=("mla", "mla_win", "mla_win", "mla_win"),
        lead_pattern=("mla",), lead_intermediate=96,
        mla=LatentAttention(heads=4, q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                            v_dim=16, rope_theta=1e4, index_heads=2, index_dim=16,
                            index_top_k=4, rescale=True, gate=True),
        mla_window=LatentAttention(heads=2, q_rank=32, kv_rank=32, nope_dim=24, rope_dim=8,
                                   v_dim=16, rope_theta=1e3, window=5, rescale=True,
                                   gate=True),
        moe_experts=8, moe_top_k=3, moe_norm_topk=True, moe_shared=32, moe_held=(0, 2),
        moe_score="sigmoid", moe_bias_rate=0.001, moe_shared_gate=False,
        moe_aux_weight=0.0001),
    # grouped-query attention by spec at test size: a leading dense layer
    # under a full mixer, then a period of three window layers and a full one;
    # 6 query heads in the window layers and 4 in the full ones over 2 kv
    # heads; a window of 5, which drops keys at 16+ positions; YaRN on half of
    # a head whose factor slows pairs 2-3 at the tests' lengths; a head-wise
    # gate; softmax routing over 8 experts, top-3 scaled by 2.5, 2 held, a
    # plain shared expert
    "window-moe-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=5, n_heads=4, n_kv_heads=2, intermediate=32,
        head_dim=16, norm_eps=1e-6,
        layer_pattern=("gqa_win", "gqa_win", "gqa_win", "gqa"),
        lead_pattern=("gqa",), lead_intermediate=96,
        gqa=GroupedQueryAttention(
            heads=4, kv_heads=2, head_dim=16, rope_theta=100.0, rotary_dim=8,
            yarn=Yarn(factor=8.0, original_length=16, beta_fast=2.0, beta_slow=0.5,
                      attention_factor=1.2), gate="headwise"),
        gqa_window=GroupedQueryAttention(
            heads=6, kv_heads=2, head_dim=16, rope_theta=1e3, window=5, gate="headwise"),
        moe_experts=8, moe_top_k=3, moe_norm_topk=True, moe_shared=32, moe_held=(0, 2),
        moe_shared_gate=False, moe_routed_scale=2.5, moe_aux_weight=0.001),
    # latent attention over every causal key at test size: a leading dense
    # layer, then a period of one expert layer; 4 heads of 8 + 4 query and key
    # features and 6 value features; YaRN in latent attention's form (factor 8
    # over 16 positions slows the second of the two pairs 8-fold, cos and sin
    # plain, the softmax scale times (0.1 ln 8 + 1)^2); a sigmoid router with
    # its bias over 12 experts, top-3 renormalised then x 2.827, 2 held
    "latent-full-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=3, n_heads=4, n_kv_heads=4, intermediate=32,
        head_dim=6, norm_eps=1e-6, layer_pattern=("mla_full",), lead_pattern=("mla_full",),
        lead_intermediate=96,
        mla_full=LatentAttentionYarn(
            heads=4, q_rank=32, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=6, rope_theta=100.0,
            yarn=Yarn(factor=8.0, original_length=16, beta_fast=1.0, beta_slow=1.0),
            softmax_factor=(0.1 * math.log(8.0) + 1.0) ** 2),
        moe_experts=12, moe_top_k=3, moe_norm_topk=True, moe_shared=32, moe_held=(0, 2),
        moe_score="sigmoid", moe_bias_rate=0.001, moe_shared_gate=False,
        moe_routed_scale=2.827, moe_aux_weight=0.0001),
    # a router ahead of attention at test size: two periods of one full layer
    # with NO rope and one roped window layer (a window of 5, which drops keys
    # at 16+ positions), 6 query heads over 2 kv heads (a group of three), no
    # leading layer, no shared expert; the router reads the block's input and
    # takes top-3 of 8 renormalised, 2 held, ReGLU experts
    "prerouted-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=6, n_kv_heads=2, intermediate=32,
        head_dim=16, norm_eps=1e-6, layer_pattern=("gqa", "gqa_win"),
        gqa=GroupedQueryAttention(heads=6, kv_heads=2, head_dim=16, rope_theta=0.0),
        gqa_window=GroupedQueryAttention(heads=6, kv_heads=2, head_dim=16, rope_theta=1e3,
                                         window=5),
        moe_experts=8, moe_top_k=3, moe_norm_topk=True, moe_held=(0, 2),
        moe_router_input="block", moe_activation="relu", moe_aux_weight=0.001),
    # block-selected attention beside lightning attention at test size: a cut
    # of a published stack of 6 (layers 0, 1 and 3, 4), one block-selected
    # layer and one of lightning attention a period; 4 query heads over 2 kv
    # heads, sets of top-4 blocks of 16 keys (the first and a 24-key window
    # forced) from keys pooled 8 at stride 4, which drop blocks at 64+
    # positions; the three multipliers all away from 1
    "sparse-linear-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=4, n_kv_heads=2, intermediate=128,
        head_dim=16, norm_eps=1e-6, layer_pattern=("block_sparse", "lightning"),
        block_sparse=BlockSparseAttention(heads=4, kv_heads=2, head_dim=16, kernel_size=8,
                                          kernel_stride=4, block_size=16, init_blocks=1,
                                          window_size=24, topk=4),
        lightning=LightningAttention(heads=4, head_dim=16, rope_theta=1e4, depth=6),
        layer_ids=(0, 1, 3, 4), embed_scale=12.0, residual_scale=1.4 / math.sqrt(6),
        logit_scale=0.25),
    # Mamba-2 layers beside un-roped grouped-query attention at test size: ONE
    # period of ten, five state-space layers, an attention layer and four more;
    # 4 heads of 32 features (four a lane tile) over a state of 16 in chunks of
    # 16, a conv of 4 taps; 4 query heads over 2 kv heads of 16 whose softmax
    # scale is a constant (1/8, not 16^-1/2); the embedding tied to the head;
    # the three multipliers all away from 1
    "granite-hybrid-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=10, n_heads=4, n_kv_heads=2, intermediate=128,
        head_dim=16, layer_pattern=("mamba2",) * 5 + ("gqa",) + ("mamba2",) * 4,
        mamba2=Mamba2(heads=4, head_dim=32, state=16, chunk=16),
        gqa=ScaledGroupedQueryAttention(heads=4, kv_heads=2, head_dim=16, rope_theta=0.0,
                                        softmax_scale=0.125),
        tie_embeddings=True, embed_scale=12.0, residual_scale=0.22, logit_scale=0.125),
    # short convolutions beside roped attention at test size: TWO leading dense
    # layers under short convolutions, then two periods of an attention layer
    # (4 query heads over 2 kv heads of 16, q and k normed a head, rope over the
    # whole head) and three short convolutions of 3 taps; a sigmoid router with
    # its bias over 8 experts, top-3 renormalised, 2 held, no shared expert; the
    # embedding tied to the head
    "conv-moe-debug": LlamaConfig(
        vocab_size=256, hidden=64, n_layers=10, n_heads=4, n_kv_heads=2, intermediate=32,
        head_dim=16, rope_theta=1e4, head_qk_norm=True,
        layer_pattern=("attn", "sconv", "sconv", "sconv"), lead_pattern=("sconv", "sconv"),
        lead_intermediate=96, moe_experts=8, moe_top_k=3, moe_norm_topk=True,
        moe_held=(0, 2), moe_score="sigmoid", moe_bias_rate=0.001, moe_aux_weight=0.0001,
        tie_embeddings=True),
}


def _attn_axes(c: LlamaConfig) -> dict:
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if c.qk_norm or c.head_qk_norm:
        axes.update(q_norm=("norm",), k_norm=("norm",))
    return axes


def _attn_init(c: LlamaConfig, keys, lead, normal) -> dict:
    H, KH, D, E = c.n_heads, c.n_kv_heads, c.head_dim, c.hidden
    params = {
        # with ``attn_out_gate`` a head's projection is its query, then its gate
        "wq": normal(keys[0], lead + (E, H, D * (2 if c.attn_out_gate else 1)), E),
        "wk": normal(keys[1], lead + (E, KH, D), E),
        "wv": normal(keys[2], lead + (E, KH, D), E),
        "wo": normal(keys[3], lead + (H, D, E), H * D),
    }
    if c.qk_norm:
        params.update(q_norm=jnp.ones(lead + (H * D,), c.dtype),
                      k_norm=jnp.ones(lead + (KH * D,), c.dtype))
    if c.head_qk_norm:
        fill = jnp.zeros if c.norm_plus_one else jnp.ones
        params.update(q_norm=fill(lead + (D,), c.dtype), k_norm=fill(lead + (D,), c.dtype))
    return params


def _dense_axes(c: LlamaConfig) -> dict:
    return {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed")}


def _dense_init(c: LlamaConfig, keys, lead, normal, width: str = "intermediate") -> dict:
    E, M = c.hidden, getattr(c, width)
    return {"w_gate": normal(keys[0], lead + (E, M), E),
            "w_up": normal(keys[1], lead + (E, M), E),
            "w_down": normal(keys[2], lead + (M, E), M)}


def _mlp_kind(c: LlamaConfig, lead: bool = False) -> LayerKind:
    """The MLP kind of the periods' blocks, or of the leading layers."""
    if lead:
        return LEAD_DENSE
    return MOE if c.moe_experts > 0 else DENSE


def _per_position(c: LlamaConfig, make):
    """``make(i, mixer)`` for each position of the period, laid out as
    ``params["layers"]`` is: the block's own tree for a period of one block
    (the layout every checkpoint and the serving engine know), else a
    sub-tree ``slot<i>`` a position."""
    if len(c.layer_pattern) == 1:
        return make(0, c.layer_pattern[0])
    return {f"slot{i}": make(i, m) for i, m in enumerate(c.layer_pattern)}


def _lead_layers(c: LlamaConfig, make) -> dict:
    """``{"lead_layers": {"layer<i>": make(i, mixer)}}`` for the leading
    layers, under a name of their own and unstacked; ``{}`` without any."""
    if not c.lead_pattern:
        return {}
    return {"lead_layers": {f"layer{i}": make(i, m) for i, m in enumerate(c.lead_pattern)}}


def param_axes(config: LlamaConfig):
    """Tree of logical-axis tuples matching ``init_params`` output."""
    c = config

    def block(_, mixer: str, lead: bool = False) -> dict:
        axes = {"attn_norm": ("norm",), **MIXERS[mixer].axes(c),
                "mlp_norm": ("norm",), **_mlp_kind(c, lead).axes(c)}
        return axes if lead else {k: ("layers",) + v for k, v in axes.items()}

    return {
        "embed": ("vocab_in", "embed"),
        **_lead_layers(c, lambda i, mixer: block(i, mixer, lead=True)),
        "layers": _per_position(c, block),
        "final_norm": ("norm",),
        **({} if c.tie_embeddings else {"lm_head": ("embed", "vocab")}),
    }


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Random init (truncated-normal fan-in scaling), stacked over the
    periods of the stack."""
    c = config
    keys = jax.random.split(key, 9)
    lead, E = (c.n_periods,), c.hidden

    def norm_init(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(c.dtype)

    norm_fill = jnp.zeros if c.norm_plus_one else jnp.ones

    def block(i: int, mixer: str, leading: bool = False) -> dict:
        # a period of one block draws from ``key`` as it always has; a leading
        # layer is one layer, unstacked
        ks = keys if len(c.layer_pattern) == 1 and not leading else jax.random.split(
            jax.random.fold_in(key, i + (1000 if leading else 0)), 9)
        stack = () if leading else lead
        leaves = {
            "attn_norm": norm_fill(stack + (E,), c.dtype),
            **MIXERS[mixer].init(c, ks[1:5], stack, norm_init),
            "mlp_norm": norm_fill(stack + (E,), c.dtype),
            **_mlp_kind(c, leading).init(c, ks[5:8], stack, norm_init),
        }
        if MIXERS[mixer].buffers is not None:
            # what a layer holds by its place in the stack: position i of
            # period p is layer len(lead_pattern) + p * len(layer_pattern) + i
            ids = [i] if leading else [len(c.lead_pattern) + p * len(c.layer_pattern) + i
                                       for p in range(c.n_periods)]
            held = MIXERS[mixer].buffers(c, ids)
            leaves.update({k: v[0] if leading else v for k, v in held.items()})
        return leaves

    return {
        "embed": norm_init(keys[0], (c.vocab_size, E), E),
        **_lead_layers(c, lambda i, mixer: block(i, mixer, leading=True)),
        "layers": _per_position(c, block),
        "final_norm": norm_fill((E,), c.dtype),
        **({} if c.tie_embeddings else {"lm_head": norm_init(keys[8], (E, c.vocab_size), E)}),
    }


def head_weights(params, config: LlamaConfig):
    """The head's matrix [E, V]: the ``lm_head`` leaf, or under
    ``tie_embeddings`` the embedding's own rows, transposed."""
    return params["embed"].T if config.tie_embeddings else params["lm_head"]


def _attention(q, k, v, config: LlamaConfig, mesh: Mesh | None):
    """Attention output [B, Hq, S, D], under the checkpoint name
    ``attn_out``. ``flash_attention``'s own vjp rule gives that name to the
    kernel's output (and ``attn_lse`` to its logsumexp), so only what
    does not come out of it is named here. Ulysses is left to the rule:
    what a saving policy keeps on an sp>1 mesh is the per-shard output
    BEFORE the all-to-all, which the flash backward reads; the all-to-all
    runs again in the backward pass for ``wo``."""
    if (config.attn_impl in ("ring", "ulysses") and mesh is not None
            and mesh.shape["sp"] > 1):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        inner = ring_attention if config.attn_impl == "ring" else ulysses_attention
        spec = P(("dcn", "dp", "fsdp"), "tp", "sp", None)
        fn = shard_map(
            functools.partial(inner, axis="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        o = fn(q, k, v)
        return checkpoint_name(o, "attn_out") if config.attn_impl == "ring" else o
    if config.attn_impl == "reference":
        return checkpoint_name(mha_reference(q, k, v, causal=True), "attn_out")
    if config.attn_impl == "none":  # ablation: identity attention
        g = q.shape[1] // k.shape[1]
        o = (q.reshape(q.shape[0], k.shape[1], g, *q.shape[2:]) * v[:, :, None]).reshape(q.shape)
        return checkpoint_name(o, "attn_out")
    return flash_per_shard(q, k, v, mesh, causal=True)


def _gate_output(attn, gate):
    """attn * sigmoid(gate), element by element, in float32."""
    return (attn.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)


def _attn_mixer(h, layer, *, config: LlamaConfig, positions, mesh: Mesh | None):
    """Softmax attention on the normed input h [B, S, E] -> [B, S, E]."""
    c = config

    def sc(t, axes):
        return shard_constraint(t, mesh, axes) if mesh is not None else t

    q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"])
    k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"])
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"])
    if c.attn_out_gate:
        q, gate = q[..., :c.head_dim], q[..., c.head_dim:]
    if c.qk_norm:
        q = norm_over_heads(q, layer["q_norm"], c.norm_eps)
        k = norm_over_heads(k, layer["k_norm"], c.norm_eps)
    if c.head_qk_norm:
        # per head, over its head_dim features, weight [D]
        q = rms_norm(q, layer["q_norm"], eps=c.norm_eps, offset=c.norm_offset)
        k = rms_norm(k, layer["k_norm"], eps=c.norm_eps, offset=c.norm_offset)
    q = apply_rope(q, positions, theta=c.rope_theta, rotary_dim=c.rotary_dim or None)
    k = apply_rope(k, positions, theta=c.rope_theta, rotary_dim=c.rotary_dim or None)
    q = checkpoint_name(sc(q, ("batch", "heads", "seq", "head_dim")), "q")
    k = checkpoint_name(k, "k")
    v = checkpoint_name(v, "v")
    attn = _attention(q, k, v, c, mesh)
    if c.attn_out_gate:
        with device_scope("attn_gate"):
            attn = _gate_output(attn, checkpoint_name(gate, "attn_gate"))
    return jnp.einsum("bhsd,hde->bse", attn, layer["wo"])


def _attn_matmul_params(c: LlamaConfig) -> float:
    gate = c.n_heads if c.attn_out_gate else 0
    return c.hidden * c.head_dim * (c.n_heads * 2 + c.n_kv_heads * 2 + gate)


def _dense_mlp(h, layer, *, config: LlamaConfig, mesh: Mesh | None, ep_axis=None):
    c = config
    gate = jnp.einsum("bse,em->bsm", h, layer["w_gate"])
    up = jnp.einsum("bse,em->bsm", h, layer["w_up"])
    ff = jax.nn.silu(gate.astype(jnp.float32)).astype(c.dtype) * up
    if mesh is not None:
        ff = shard_constraint(ff, mesh, ("batch", "seq", "mlp"))
    return jnp.einsum("bsm,me->bse", ff, layer["w_down"]), {}


ATTN = LayerKind(
    axes=_attn_axes, init=_attn_init, apply=_attn_mixer,
    matmul_params=_attn_matmul_params,
    # causal scores and values, forward: 2 products x 2 H D seq / 2
    mixing_flops=lambda c, seq: 2.0 * c.n_heads * c.head_dim * seq,
    # what ``flash_attention``'s vjp rule reads: the projections, and the
    # two residuals it names itself; the output gate where there is one
    save_names=("q", "k", "v", "attn_out", "attn_lse", "attn_gate"))
DENSE = LayerKind(
    axes=_dense_axes, init=_dense_init, apply=_dense_mlp,
    matmul_params=lambda c: 3.0 * c.hidden * c.intermediate)
LEAD_DENSE = LayerKind(
    axes=_dense_axes, init=functools.partial(_dense_init, width="lead_intermediate"),
    apply=_dense_mlp, matmul_params=lambda c: 3.0 * c.hidden * c.lead_intermediate)
MIXERS: dict[str, LayerKind] = {"attn": ATTN, "gdn": GDN, "mla": MLA, "mla_win": MLA_WINDOW,
                                "mla_full": MLA_FULL, "gqa": GQA, "gqa_win": GQA_WINDOW,
                                "lightning": LIGHTNING, "block_sparse": BLOCK_SPARSE,
                                "mamba2": MAMBA2, "sconv": SCONV}


def _scaled(t, scale: float):
    """t times a fixed multiplier, in float32, rounded once; t itself at 1."""
    if scale == 1.0:
        return t
    return (t.astype(jnp.float32) * scale).astype(t.dtype)


def _block(x, layer, positions, config: LlamaConfig, mesh: Mesh | None,
           ep_axis: str | None = None, mixer: str = "attn", lead: bool = False,
           return_selection: bool = False):
    """One decoder block: x [B, S, E] in config.dtype -> (x, aux, mixed_aux).
    ``aux`` is ``{}`` for a dense MLP and ``moe_block``'s for a routed one;
    ``mixed_aux`` what the mixer counted beside its output (``{}`` for most).
    ``ep_axis`` is set only when running per-device inside the pipeline
    shard_map (expert shard + psum combine); ``lead``: a leading layer,
    whose MLP is the leading kind; ``return_selection`` asks an indexed mixer
    for its key sets too (comparisons only)."""
    c = config

    def sc(t, axes):
        return shard_constraint(t, mesh, axes) if mesh is not None else t

    # scopes are text: ``rt_scope="stack/attn"`` in each device op's own
    # name on the profiler's op line (``tracing.device_scope``; ``op_name``
    # in the compiled text's metadata too), and leave the compiled program
    # as it was
    mlp = _mlp_kind(c, lead)
    early = None
    if mlp.early is not None:
        # what the MLP takes from the block's input alone: under its own
        # scopes, here, where that input is ready and the mixer has not run
        with device_scope("mlp"):
            early = mlp.early(x, layer, config=c)
    with device_scope("attn"):
        h = rms_norm(x, layer["attn_norm"], eps=c.norm_eps, offset=c.norm_offset)
        mixed = MIXERS[mixer].apply(h, layer, config=c, positions=positions, mesh=mesh,
                                    **({"return_selection": True} if return_selection else {}))
        mixed, mixed_aux = mixed if isinstance(mixed, tuple) else (mixed, {})
        # a name and nothing else: a kind whose ``save_names`` lists it keeps
        # the stream under remat ``attn`` and its second run makes no ``wo``
        # product (the latent kinds, models/mla.py; the grouped-query kinds,
        # models/gqa.py; the short conv); unlisted, no program moves
        x = checkpoint_name(
            x + sc(_scaled(mixed, c.residual_scale), ("batch", "seq", "embed_act")), POST_ATTN)

    with device_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], eps=c.norm_eps, offset=c.norm_offset)
        down, aux = mlp.apply(h, layer, config=c, mesh=mesh, ep_axis=ep_axis,
                              **({} if early is None else {"early": early}))
        x = x + sc(_scaled(down, c.residual_scale), ("batch", "seq", "embed_act"))
    return x, aux, mixed_aux


def _apply_remat(block, c: LlamaConfig, mixer: str = "attn", lead: bool = False):
    """Wrap a decoder block with the configured rematerialisation policy."""
    if not c.remat:
        return block
    if c.remat_policy == "dots":
        return jax.checkpoint(
            block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if c.remat_policy == "attn":
        # save the attention path: the q/k/v projections and the two
        # residuals flash_attention's vjp rule names, the kernel's output
        # and its compact logsumexp (~2.7 GB at 8x2048 for 1b), so the
        # backward's recompute skips the attention forward entirely — the
        # best HBM/FLOPs trade on a 16 GB chip. On a v5e, internlm2-1.8b
        # at 2x4096: flash_fwd runs 24 times a step, not 48 (36 ms of an
        # 869 ms step), and with the backward kernels reading lse as rows
        # the step takes 816.8 ms; the saved lse is 12.6 MB in all, its
        # slice costs 0.09 ms a layer (PERF.md, PR 25)
        # A routed MLP adds what its routing produced (models/moe.py's
        # ROUTE_NAMES: the softmax, the gates, the sort and the group
        # sizes, ~6 MB a layer at 16k tokens), so the backward pass
        # neither routes nor sorts again. The sorted expert inputs and
        # the gate and up products (``moe_xs``, ``moe_gate``, ``moe_up``:
        # 537 + 2 x 268 MB a layer there) are recomputed, one row gather
        # and two grouped matmuls a layer: by the chip compiler's count
        # OLMoE at 3 layers and 4 x 4096 is 13.79 GB so, and 17.02 GB
        # with gate and up saved (PERF.md, PR 26).
        # A DeltaNet mixer saves what its backward reads and cannot cheaply
        # remake (models/gdn.py's SAVE_NAMES: q, k and v as the conv takes
        # them, the gates, the scan's output and z, ~0.54 GB a layer at 16k
        # tokens); the conv, the chunk-local operands and the chunk states
        # are made again.
        # A latent-attention mixer (models/mla.py's SAVE_NAMES) saves the
        # latents, not the per-head q, k, v made from them, the kernel's
        # output and logsumexp, the gate, the key sets, and since PR 52
        # ``kinds.POST_ATTN``, the stream as the mixer's output joins it:
        # the second run needs the mixer's output for nothing else, and
        # remaking it is ``wo``, 16,384 (128 heads) or 8,192 (64) FLOPs a
        # saved byte against 1,024-2,048 for q, k, v.
        # The grouped-query kinds by spec (models/gqa.py's SAVE_NAMES, one
        # tuple for ``gqa`` and ``gqa_win``) save the stream too since PR 59:
        # ``heads x head_dim`` FLOPs a saved byte (6,144-9,216 in Laguna) and
        # never less than ``hidden``, what a byte of their saved q spares.
        # This module's own ``attn`` kind does not list it: ``train-4k`` has
        # no room for 24 streams (15.12 GB).
        # The names are the kinds' own, the block's mixer's and its MLP's,
        # but for POST_ATTN, which ``_block`` gives and a mixer kind lists.
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_only_these_names(
                *MIXERS[mixer].save_names, *_mlp_kind(c, lead).save_names,
            ),
        )
    return jax.checkpoint(block)


def forward_hidden(params, tokens, config: LlamaConfig, *, mesh: Mesh | None = None,
                   return_aux: bool = False, return_selection: bool = False):
    """tokens [B, S] int32 -> final hidden states [B, S, E] in config.dtype.

    ``return_aux=True`` additionally returns what the routed layers
    counted in the same pass: ``load_balance`` and ``z`` (the auxiliary
    terms, each the mean over layers), ``rows_per_expert`` [L, X] int32,
    ``rows_dropped`` (0: the dispatch is dropless), where the program
    holds a share of the experts (``moe_held``), ``rows_per_held_expert``
    [L, count] and ``held_share`` [L] (their sum over all rows), and under
    ReGLU experts ``act_zero`` [L] (the share of the computed rows' gate
    products that the activation zeroed). ``{}`` for
    dense configs and on the pipelined path, which does not thread it
    through the schedule yet. A window mixer that counts its pairs
    (``gqa_win``) adds ``attn_window_share``, the mean over those layers. An
    indexed mixer (``mla``) adds ``index_loss``
    and ``attn_selected_share``, each the mean over the indexed layers, and
    with ``return_selection`` the key sets themselves, ``selection``
    [indexed layers, B, S, S] int8 in layer order (for a comparison; a
    training step does not ask). A block-selected mixer (``block_sparse``) adds
    ``attn_block_kept_share``, ``attn_block_forced_share`` and
    ``attn_block_tile_share``, each the mean over those layers, and with
    ``return_selection`` its sets [those layers, B, KV, S, S / block]. A
    state-space mixer (``mamba2``) adds ``ssm_decay_mean``, the mean of its
    decays ``exp(dt A)`` over those layers, heads and positions. A short
    convolution (``sconv``) adds ``sconv_past_share``, the mean over those layers
    of the share of the conv's output that earlier positions give."""
    c = config
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    with device_scope("embed"):
        if mesh is None or mesh.size == 1:
            # XLA's gather, whose gradient is the kernel ``moe_rows`` (a
            # float32 sum in VMEM of the rows that name a token id) where the
            # gather's own transpose is a scatter-add of rows into a float32
            # [vocab, E] of zeros: 2-4 us a 10-14 KB row on a v5e. Under a
            # mesh of more than one device the table may be sharded and keeps
            # the plain lookup: the partitioner would gather the table for a
            # ``pallas_call``.
            x = take_rows(params["embed"], tokens.reshape(b * s)).reshape(b, s, -1)
        else:
            x = params["embed"][tokens]
        x = _scaled(x, c.embed_scale).astype(c.dtype)
    if mesh is not None:
        # Two-hop resharding. The gather's output inherits the table's
        # embed=fsdp sharding; jumping straight to batch=(dcn,dp,fsdp)
        # asks SPMD for a transition it can only do by replicating the
        # whole tensor (the dryrun's "Involuntary full rematerialization"
        # warning on dcn meshes). Hop 1 reshards batch/seq while KEEPING
        # embed on fsdp; hop 2 moves fsdp from embed to batch — each a
        # single-axis change XLA lowers to cheap collectives.
        if mesh.shape.get("fsdp", 1) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as _P

            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, _P(("dcn", "dp"), "sp", "fsdp")))
        x = shard_constraint(x, mesh, ("batch", "seq", "embed_act"))

    if mesh is not None and "pp" in mesh.shape and mesh.shape["pp"] > 1:
        # Pipelined path: stages over the pp axis, microbatch schedule via
        # shard_map + ppermute (parallel/pipeline.py). Blocks run as pure
        # per-device compute; MoE experts shard over ep inside the
        # shard_map (psum combine).
        from jax.sharding import PartitionSpec as P

        from ..parallel.pipeline import pipeline_apply

        if len(c.layer_pattern) > 1:
            raise NotImplementedError("the pipeline schedule takes a period of one block")
        ep_axis = "ep" if c.moe_experts > 0 and mesh.shape.get("ep", 1) > 1 else None
        raw_block = functools.partial(
            _block, positions=positions, config=c, mesh=None, ep_axis=ep_axis
        )
        block = _apply_remat(lambda carry, layer: raw_block(carry, layer)[0], c)
        # per-param specs: layers dim over pp; EXPERT WEIGHT dims over ep.
        # The router stays replicated across ep — routing is global (every
        # device scores all experts, then computes only its local shard).
        expert_weights = ("w_gate", "w_up", "w_down")
        param_specs = {
            name: (P("pp", "ep") if (ep_axis and c.moe_experts > 0 and name in expert_weights)
                   else P("pp"))
            for name in param_axes(c)["layers"]
        }
        with device_scope("stack"):
            x = pipeline_apply(
                block, params["layers"], x,
                mesh=mesh, n_microbatches=c.pipeline_microbatches,
                param_specs=param_specs,
            )
        out = _scaled(rms_norm(x, params["final_norm"], eps=c.norm_eps, offset=c.norm_offset),
                      c.logit_scale)
        return (out, {}) if return_aux else out

    # leading layers: outside the scan, each its own block, their MLP the
    # leading kind (a dense one: no aux)
    mixed_auxes = []
    for i, mixer in enumerate(c.lead_pattern):
        with device_scope("stack"):
            x, _, mixed_aux = _apply_remat(
                functools.partial(_block, positions=positions, config=c, mesh=mesh,
                                  mixer=mixer, lead=True, return_selection=return_selection),
                c, mixer, lead=True)(
                x, params["lead_layers"][f"layer{i}"])
        mixed_auxes.append(mixed_aux)

    blocks = [
        _apply_remat(functools.partial(_block, positions=positions, config=c, mesh=mesh,
                                       mixer=mixer, return_selection=return_selection),
                     c, mixer)
        for mixer in c.layer_pattern]
    # a period of one block keeps its leaves unnested (``_per_position``): it
    # is the period whose one position is that tree
    layers = params["layers"] if len(blocks) > 1 else {"slot0": params["layers"]}

    def period(x, layers):
        auxes, mixed = [], []
        for i, block in enumerate(blocks):
            x, aux, mixed_aux = block(x, layers[f"slot{i}"])
            auxes.append(aux)
            mixed.append(mixed_aux)
        return x, (auxes, mixed)

    # one aux a position of the period, each [periods, ...] -> [L, ...] in
    # layer order: position i of period p is scanned layer p * len(blocks) + i
    # ``stack``: what the scan itself costs (the saved residuals stacked by
    # ``dynamic-update-slice``, the slices of the layer stack) has a name
    with device_scope("stack"):
        x, (auxes, mixed) = lax.scan(period, x, layers)
    scanned = c.n_layers - len(c.lead_pattern)
    per_layer = auxes[0] if len(auxes) == 1 else jax.tree.map(
        lambda *a: jnp.stack(a, axis=1).reshape((scanned,) + a[0].shape[1:]), *auxes)
    out = _scaled(rms_norm(x, params["final_norm"], eps=c.norm_eps, offset=c.norm_offset),
                  c.logit_scale)
    if not return_aux:
        return out
    aux = {}
    if per_layer:
        aux = {"load_balance": jnp.mean(per_layer["load_balance"]),
               "z": jnp.mean(per_layer["z"]),
               "rows_per_expert": per_layer["rows"],
               "rows_dropped": jnp.sum(per_layer["dropped"])}
        if "rows_held" in per_layer:
            aux.update(rows_per_held_expert=per_layer["rows_held"],
                       held_share=per_layer["held_share"])
        if "act_zero" in per_layer:
            aux["act_zero"] = per_layer["act_zero"]
    # what the mixers counted: a leading layer's is a scalar, a period
    # position's [periods]; the mean over every layer that counted it
    counted = mixed_auxes + mixed
    both = lambda name: jnp.mean(jnp.concatenate(  # noqa: E731
        [jnp.ravel(m[name]) for m in counted if name in m]))
    if any("window_share" in m for m in counted):
        aux["attn_window_share"] = both("window_share")
    if any("index_loss" in m for m in counted):
        aux.update(index_loss=both("index_loss"),
                   attn_selected_share=both("selected_share"))
    if any("decay_mean" in m for m in counted):
        aux["ssm_decay_mean"] = both("decay_mean")
    if any("past_share" in m for m in counted):
        aux["sconv_past_share"] = both("past_share")
    if any("block_kept_share" in m for m in counted):
        aux.update({f"attn_{name}": both(name) for name in (
            "block_kept_share", "block_forced_share", "block_tile_share")})
    if return_selection and any("selection" in m for m in counted):
        # a leading layer's [B, S, S]; a period position's [periods, B, S, S],
        # its layers ``len(blocks)`` apart (a block-selected layer's sets are
        # [B, KV, S, S / block] in the same places)
        sets = [m["selection"][None] for m in mixed_auxes if "selection" in m]
        scanned_sets = [m["selection"] for m in mixed if "selection" in m]
        if scanned_sets:
            sets.append(jnp.stack(scanned_sets, axis=1).reshape(
                (-1,) + scanned_sets[0].shape[1:]))
        aux["selection"] = jnp.concatenate(sets)
    return out, aux


def forward(params, tokens, config: LlamaConfig, *, mesh: Mesh | None = None):
    """tokens [B, S] int32 -> logits [B, S, vocab] f32. For inference/tests;
    training uses ``loss_fn`` which never materializes full logits."""
    x = forward_hidden(params, tokens, config, mesh=mesh)
    logits = jnp.einsum("bse,ev->bsv", x, head_weights(params, config))
    return logits.astype(jnp.float32)


def train_flops_per_token(config: LlamaConfig, seq: int) -> float:
    """Model FLOPs per trained token (6N active-param matmul + what the
    mixers compute that is no parameter product: causal attention, the
    delta rule's chunks), the numerator of MFU; each kind counts its own.
    Embedding gather excluded (standard accounting); a routed MLP counts
    what the dropless dispatch computes for a token: its top_k experts'
    three matrices (with ``moe_held``, the held experts' share of them in
    expectation), the shared expert, plus the router."""
    c = config
    mlp = _mlp_kind(c).matmul_params(c)
    lead_mlp = _mlp_kind(c, lead=True).matmul_params(c)
    n_params = c.n_periods * sum(
        MIXERS[m].matmul_params(c) + mlp for m in c.layer_pattern
    ) + sum(MIXERS[m].matmul_params(c) + lead_mlp for m in c.lead_pattern
            ) + c.hidden * c.vocab_size
    mixing = c.n_periods * sum(MIXERS[m].mixing_flops(c, seq) for m in c.layer_pattern
                               ) + sum(MIXERS[m].mixing_flops(c, seq) for m in c.lead_pattern)
    return 6.0 * n_params + 3 * mixing


def _head_chunks(mesh, hs, lm_head, ts, ms, denom, with_grads: bool):
    """One scan over token chunks of the head's cross entropy.

    hs [nc, c, E], ts / ms [nc, c], denom a float32 scalar. Returns the
    mean cross entropy ``-sum(ms * log p(ts)) / denom`` and, when
    ``with_grads``, its gradients ``(d_hs [nc, c, E], d_lm_head [E, V])``
    from the same logits. One ``[c, V]`` float32 block exists at a time."""
    contract = lambda a, b, i, j: lax.dot_general(
        a, b, (((i,), (j,)), ((), ())), preferred_element_type=jnp.float32)

    def chunk(carry, xs):
        h, t, m = xs
        logits = jnp.einsum("ce,ev->cv", h, lm_head, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0] - lse
        if not with_grads:
            return carry + (ll * m).sum(), None
        total, d_w = carry
        # float32 into both products, as autodiff hands it to them; the
        # weights' gradient is rounded and summed over chunks in the
        # weights' type, as a scan's transpose sums a cotangent
        dlogits = ((jnp.exp(logits - lse[:, None])
                    - jax.nn.one_hot(t, logits.shape[-1], dtype=jnp.float32))
                   * (m / denom)[:, None])
        d_h = contract(dlogits, lm_head, 1, 1).astype(h.dtype)
        d_w = d_w + contract(h, dlogits, 0, 0).astype(d_w.dtype)
        return (total + (ll * m).sum(), d_w), d_h

    zero = jnp.zeros((), jnp.float32)
    if not with_grads:
        total, _ = lax.scan(chunk, zero, (hs, ts, ms))
        return -total / denom
    d_w = jnp.zeros_like(lm_head)
    if mesh is not None:
        d_w = shard_constraint(d_w, mesh, ("embed", "vocab"))
    (total, d_w), d_hs = lax.scan(chunk, (zero, d_w), (hs, ts, ms))
    return -total / denom, (d_hs, d_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _head_loss(mesh, hs, lm_head, ts, ms, denom):
    """Chunked mean cross entropy of ``hs @ lm_head`` against ``ts`` under
    the weights ``ms / denom``. Differentiated, the pass that makes the
    loss makes its gradients too (``_head_chunks``): the backward rule
    only scales them. Not differentiated, it is the loss alone."""
    return _head_chunks(mesh, hs, lm_head, ts, ms, denom, False)


def _head_loss_fwd(mesh, hs, lm_head, ts, ms, denom):
    return _head_chunks(mesh, hs, lm_head, ts, ms, denom, True)


def _head_loss_bwd(mesh, grads, g):
    d_hs, d_w = grads
    return ((g * d_hs).astype(d_hs.dtype), (g * d_w).astype(d_w.dtype),
            None, None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def loss_fn(
    params,
    batch,
    config: LlamaConfig,
    *,
    mesh: Mesh | None = None,
    chunk_tokens: int = 512,
    return_aux: bool = False,
):
    """Next-token cross entropy, plus a routed model's auxiliary terms
    (``moe_aux_weight`` x load balance + ``moe_z_weight`` x router z-loss).
    batch: {"tokens": [B,S], "mask": [B,S]}. ``return_aux=True`` returns
    ``(loss, aux)`` for ``value_and_grad(has_aux=True)``: ``ce`` and, for a
    routed model, ``forward_hidden``'s counters from the same pass.

    The lm_head matmul runs inside a scan over chunks of ``chunk_tokens``
    tokens, so the [B,S,vocab] logits tensor never exists in HBM — at 128k
    vocab that tensor alone would OOM a v5e chip at batch 8 × 2048. The
    scan is one ``custom_vjp`` (``_head_loss``): under ``grad`` each chunk
    computes its logits once and from them the loss, ``softmax - onehot``
    and straight away the gradients of the hidden rows and of ``lm_head``,
    three products over the vocabulary a chunk where a rematerialized
    chunk ran four (the logits twice). A call that is not differentiated
    computes no gradient: one product a chunk. The mask and the tokens get
    no cotangent.

    Every op carries the pass it runs in beside its scope (``rt_pass`` =
    ``fwd`` / ``remat`` / ``bwd``: ``tracing.with_passes``); ``aux`` comes
    from the forward pass and is not differentiated (giving it a cotangent
    is refused: differentiate ``_loss`` for that).
    """
    loss, aux = with_passes(
        lambda p, b: _loss(p, b, config, mesh=mesh, chunk_tokens=chunk_tokens),
        has_aux=True)(params, batch)
    return (loss, aux) if return_aux else loss


def _loss(params, batch, config: LlamaConfig, *, mesh: Mesh | None, chunk_tokens: int):
    """``loss_fn``'s ``(loss, aux)``, under no pass."""
    tokens = batch["tokens"]
    hidden, aux = forward_hidden(params, tokens, config, mesh=mesh, return_aux=True)
    with device_scope("lm_head_loss"):
        targets = tokens[:, 1:]
        hidden = hidden[:, :-1]
        mask = batch.get("mask")
        mask = (jnp.ones_like(targets, jnp.float32) if mask is None
                else mask[:, 1:].astype(jnp.float32))

        b, s, e = hidden.shape
        n = b * s
        flat_h = hidden.reshape(n, e)
        flat_t = targets.reshape(n)
        flat_m = mask.reshape(n)
        chunk = min(chunk_tokens, n)
        if n % chunk:
            pad = chunk - n % chunk
            flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
            flat_t = jnp.pad(flat_t, (0, pad))
            flat_m = jnp.pad(flat_m, (0, pad))
            n += pad
        nc = n // chunk
        ce = _head_loss(
            mesh, flat_h.reshape(nc, chunk, e), head_weights(params, config),
            flat_t.reshape(nc, chunk), flat_m.reshape(nc, chunk),
            jnp.maximum(flat_m.sum(), 1.0))
    loss = ce
    if "load_balance" in aux:
        loss = (ce + config.moe_aux_weight * aux["load_balance"]
                + config.moe_z_weight * aux["z"])
    if "index_loss" in aux:
        # reaches the indexers' leaves and no other: their inputs are cut
        # from the graph, and the model's loss passes no gradient to a top-k.
        # No weight: the two terms' gradients touch disjoint leaves
        with device_scope("index_loss"):
            loss = loss + aux["index_loss"]
    return loss, {"ce": ce, **aux}


def update_buffers(params, aux, config: LlamaConfig):
    """The leaves no gradient moves, stepped from a step's own counters
    (``loss_fn(..., return_aux=True)``'s ``aux``), after the optimizer's
    update: the routers' selection bias (``models/moe.py::bias_step``), a
    layer's from that layer's rows per expert. Params come back unchanged
    where the model has no such leaf."""
    c = config
    if not (c.moe_experts and c.moe_bias_rate):
        return params
    rows = aux["rows_per_expert"].reshape(c.n_periods, len(c.layer_pattern), -1)
    layers = params["layers"] if len(c.layer_pattern) > 1 else {"slot0": params["layers"]}
    stepped = {
        slot: {**layer, "router_bias": bias_step(
            layer["router_bias"], rows[:, int(slot[4:])], c.moe_bias_rate)}
        for slot, layer in layers.items()}
    return {**params, "layers": stepped if len(c.layer_pattern) > 1 else stepped["slot0"]}
