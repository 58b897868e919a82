"""Grouped-query attention over a set of key blocks that the layer chooses
with no parameters of its own (InfLLM-v2: MiniCPM4, arXiv 2506.07900, section
2.2; MiniCPM-SALA's ``minicpm4`` layers), as ``models/mla.py``'s indexed kind
chooses single keys with a learned indexer.

For the normed input ``h`` of a position, H query heads over KV kv heads of D
features (head j reads kv head ``j // (H / KV)``), no rope at all:

    q = norm_D(h W_q);  k = norm_D(h W_k);  v = h W_v
    B_g[t]  = select_blocks(q, k)                   one set a kv group g
                                                    (``ops/block_select.py``)
    o_j[t]  = softmax over the keys s <= t in B_g[t] of (D^-1/2 q_j[t] . k[s]) v[s]
    y       = (o * sigmoid(h W_g)) W_o              the gate element-wise

The sets are [B, KV, T, T / 64] int8 and ``flash_attention(block_sets=)``
spreads a tile's flags over its keys in VMEM (``attn_blk_*``): no [T, T] array
exists. This first form computes EVERY tile of the causal triangle and masks
inside it; a query keeps 64 blocks of 64 keys, so at 16k it computes 8,192.5
keys a query to keep 3,560. The selection carries no gradient. While a query
sees at most ``topk`` blocks its set is every block, and the layer is dense
causal attention (a row shorter than ``topk x block_size``, and the first
4,096 queries of any row).

The mixer counts beside its output: ``block_kept_share`` (attended pairs over
causal pairs), ``block_forced_share`` (the share of a set's blocks that are
the first or the window's, chosen whatever the weights) and
``block_tile_share`` (the kernels' computed tiles over the causal triangle's
live tiles: 1 in this form). Named scopes ``sparse_select`` and ``sparse_attn``
split a layer on the trace.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import flash_attention, rms_norm
from ..ops.block_select import select_blocks, set_counters
from .kinds import LayerKind, sigmoid_gate

SAVE_NAMES = ("q", "k", "v", "sparse_sets", "attn_out", "attn_lse", "attn_gate")
SIZES = ("kernel_size", "kernel_stride", "block_size", "init_blocks", "window_size", "topk")


@dataclasses.dataclass(frozen=True)
class BlockSparseAttention:
    """The widths of the block-selected attention layers and the six sizes of
    their selection (``sparse_config``)."""

    heads: int
    kv_heads: int
    head_dim: int
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over {self.kv_heads} kv heads")

    @property
    def sizes(self) -> dict:
        return {name: getattr(self, name) for name in SIZES}


def _axes(c) -> dict:
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "q_norm": ("norm",), "k_norm": ("norm",),
        "w_attn_gate": ("embed", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def _init(c, keys, lead, normal) -> dict:
    a = c.block_sparse
    e, h, kh, d = c.hidden, a.heads, a.kv_heads, a.head_dim
    return {
        "wq": normal(keys[0], lead + (e, h, d), e),
        "wk": normal(keys[1], lead + (e, kh, d), e),
        "wv": normal(keys[2], lead + (e, kh, d), e),
        "q_norm": jnp.ones(lead + (d,), c.dtype),
        "k_norm": jnp.ones(lead + (d,), c.dtype),
        "w_attn_gate": normal(jax.random.fold_in(keys[0], 1), lead + (e, h, d), e),
        "wo": normal(keys[3], lead + (h, d, e), h * d),
    }


def block_sparse_mixer(h, layer, *, config, positions, mesh=None,
                       return_selection: bool = False):
    """h [B, S, E] (normed) -> (y [B, S, E], aux). ``return_selection`` adds
    the sets to ``aux`` (a comparison hands them to the reference)."""
    c, a = config, config.block_sparse
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("block-selected attention runs on one device: its "
                                  "selection has no per-shard call yet")
    s = h.shape[1]
    q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"])
    k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"])
    v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"])
    q = checkpoint_name(rms_norm(q, layer["q_norm"], eps=c.norm_eps), "q")
    k = checkpoint_name(rms_norm(k, layer["k_norm"], eps=c.norm_eps), "k")
    v = checkpoint_name(v, "v")
    short = -s % a.block_size
    if short:
        # whole blocks: the rows after the last are keys no query may see
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, short), (0, 0))) for t in (q, k, v))
    scale = a.head_dim ** -0.5
    with device_scope("sparse_select"):
        sets = checkpoint_name(select_blocks(q, k, sm_scale=scale, **a.sizes), "sparse_sets")
        counted = set_counters(sets[:, :, :s], block_size=a.block_size,
                               init_blocks=a.init_blocks, window_size=a.window_size)
    with device_scope("sparse_attn"):
        attn = flash_attention(q, k, v, causal=True, sm_scale=scale, block_sets=sets,
                               set_block=a.block_size)[:, :, :s]
    aux = {"block_kept_share": counted["kept_share"],
           "block_forced_share": counted["forced_share"],
           # every live tile of the causal triangle is computed in this form
           "block_tile_share": jnp.float32(1.0)}
    if return_selection:
        aux["selection"] = sets
    with device_scope("attn_gate"):
        attn = sigmoid_gate(h, layer["w_attn_gate"], attn)
    return jnp.einsum("bhsd,hde->bse", attn, layer["wo"]), aux


def mean_set_keys(a: BlockSparseAttention, seq: int) -> float:
    """Mean keys a query of a ``seq``-long row attends: ``topk`` blocks (every
    block while it sees fewer), its own block up to itself."""
    t = np.arange(seq)
    blocks = np.minimum(t // a.block_size + 1, a.topk)
    return float((blocks * a.block_size - (a.block_size - 1 - t % a.block_size)).mean())


def _matmul_params(c) -> float:
    a = c.block_sparse
    return c.hidden * a.head_dim * (3.0 * a.heads + 2 * a.kv_heads)   # q, gate, out; k, v


def _mixing_flops(c, seq: int) -> float:
    """Forward FLOPs a token: scores and values over the keys a query KEEPS,
    and the selection's scores against the pooled keys it may see (a pooled
    key a stride; a query sees those that end before it: half of them)."""
    a = c.block_sparse
    return 2.0 * a.heads * a.head_dim * (2 * mean_set_keys(a, seq) + seq / a.kernel_stride / 2)


BLOCK_SPARSE = LayerKind(axes=_axes, init=_init, apply=block_sparse_mixer,
                         matmul_params=_matmul_params, mixing_flops=_mixing_flops,
                         save_names=SAVE_NAMES)

__all__ = ["BLOCK_SPARSE", "BlockSparseAttention", "SAVE_NAMES", "block_sparse_mixer",
           "mean_set_keys"]
