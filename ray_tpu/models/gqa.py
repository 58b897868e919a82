"""Grouped-query softmax attention as two token mixers whose widths a spec
owns, as ``models/mla.py`` is two of latent attention: ``gqa`` (every causal
key) and ``gqa_win`` (a causal window), each described by a
``GroupedQueryAttention`` on the config (``gqa``, ``gqa_window``). One model
may so hold two head counts, two ropes and a window side by side
(Laguna-S-2.1: 48 query heads under YaRN on half of a head in its full
layers, 72 under plain rope and a 512-key window in the others, 8 kv heads
of 128 in both; SmallThinker: full layers with NO rope, whose only position
signal is the causal mask, beside roped layers under a 4,096-key window, 28
query heads over 4 kv heads in both).

The ``attn`` kind of ``models/llama.py`` reads the config's own top-level
widths and is NOT built from this spec. It is the one kind with what no
other model of grouped queries here has: two q/k norms (over a head, over
the whole projection), Qwen3-Next's element-wise gate cut from a q
projection twice as wide, and three ways to run the scores (ring and
Ulysses over ``sp``, a plain reference). A spec that carried all of that
would be ``LlamaConfig`` again under another name, and the four accepted
cells that run ``attn`` are held to the byte of their lowered step. What
the two share is shared: the per-shard kernel call under a mesh
(``kinds.flash_per_shard``), the head-wise gate and ``kept_keys``
(``models/kinds.py``), rope (``ops/rope.py``).

For the normed input ``x`` of a position, H query heads and KV key/value
heads of D features (head h reads kv head ``h // (H / KV)``):

    q_h = x W_q[h];  k_j = x W_k[j];  v_j = x W_v[j];  rope on q and k
                                              (``rope_theta`` 0: none, q and
                                               k go to the scores as projected)
    o_h = softmax over the allowed keys of (q_h . k_j D^-1/2) v_j
                                             (``softmax_scale`` set: that
                                              constant in D^-1/2's place;
                                              Granite's 1/64 at heads of 64)
    y   = concat_h(sigmoid(x W_g)_h o_h) W_o          (``gate`` "headwise")

Allowed keys of query t: s <= t, and with ``window`` t - s < window. Rope
turns the first ``rotary_dim`` features of a head (0: all) by ``rope_theta``'s
frequencies, or with ``yarn`` by YaRN's (``ops/rope.py``), cos and sin times
its ``attention_factor``. A window layer's kernels run in blocks that follow
the window and the group (``window_blocks``): with more than one query head a
kv head, a tile's rows are the kv head's whole group x a short query block.

The mixer counts beside its output, where it has a window, ``window_share``:
the (query, key) pairs it attends over the causal pairs, from the positions
it was given.

``SAVE_NAMES``, one tuple for both kinds, is what remat ``attn`` keeps of a
block: q, k and v as the kernel takes them, the kernel's output and
logsumexp, the gate, and since PR 59 ``kinds.POST_ATTN``, the residual stream
as the mixer's output joins it (the stack's name: ``models/llama.py::_block``
gives it). No gradient reads the mixer's output, so the block's second run
then makes neither ``wo``'s product nor the add. A saved byte of that stream
spares ``heads x head_dim`` FLOPs of second run (6,144-9,216 in Laguna, 3,584
in SmallThinker, 2,048 in Granite's one layer) where a saved byte of q spares
``hidden`` (3,072 / 2,560 / 2,048): never less. The SUM and not the mixer's
output, which would be rounded once more before the add (``models/mla.py``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import apply_rope
from ..parallel.sharding import shard_constraint
from .kinds import POST_ATTN, LayerKind, Yarn, flash_per_shard, sigmoid_gate, kept_keys, rope_keywords

SAVE_NAMES = ("q", "k", "v", "attn_out", "attn_lse", "attn_gate", POST_ATTN)


@dataclasses.dataclass(frozen=True)
class GroupedQueryAttention:
    """The widths of one kind of grouped-query attention layer."""

    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float         # 0: no rope at all (the causal mask alone)
    rotary_dim: int = 0       # 0: rope on every feature of a head
    yarn: Yarn | None = None
    window: int = 0           # 0: none. Else query t sees keys t - window + 1 .. t
    gate: str = "none"        # "none" | "headwise"
    # what a spec with a published softmax constant states
    # (``ScaledGroupedQueryAttention``'s field); here a fact of the class:
    # the scores are scaled by head_dim ** -0.5
    softmax_scale = None

    def __post_init__(self):
        if self.gate not in ("none", "headwise"):
            raise ValueError(f"gate is {self.gate!r}: 'none' or 'headwise'")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over {self.kv_heads} kv heads")


@dataclasses.dataclass(frozen=True)
class ScaledGroupedQueryAttention(GroupedQueryAttention):
    """The widths of a grouped-query attention layer whose softmax scale is a
    published constant (Granite's ``attention_multiplier``: 1/64 at heads of
    64, not 64^-1/2); a spec of its own: the benchmark's accepted tests hold
    ``GroupedQueryAttention`` to its eight fields."""

    softmax_scale: float | None = None   # None: head_dim ** -0.5


def _axes(a: GroupedQueryAttention) -> dict:
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if a.gate == "headwise":
        axes["w_attn_gate"] = ("embed", "heads")
    return axes


def _init(a: GroupedQueryAttention, c, keys, lead, normal) -> dict:
    e, h, kh, d = c.hidden, a.heads, a.kv_heads, a.head_dim
    params = {
        "wq": normal(keys[0], lead + (e, h, d), e),
        "wk": normal(keys[1], lead + (e, kh, d), e),
        "wv": normal(keys[2], lead + (e, kh, d), e),
        "wo": normal(keys[3], lead + (h, d, e), h * d),
    }
    if a.gate == "headwise":
        params["w_attn_gate"] = normal(jax.random.fold_in(keys[0], 1), lead + (e, h), e)
    return params


def _rope(t, positions, a: GroupedQueryAttention):
    if not a.rope_theta:
        return t
    rotary = a.rotary_dim or a.head_dim
    return apply_rope(t, positions, rotary_dim=rotary,
                      **rope_keywords(rotary, a.rope_theta, a.yarn))


# Blocks of a window longer than 512 keys. At `1 x 28 x 16384 x 128` over 4 kv
# heads under a 4,096-key window, the kernels alone on a v5e (my chip run, PR
# 45: timed calls, q x k blocks, forward / forward and backward, ms): 512 x 512
# 13.0 / 37.7 (a band of 9 tiles, 8 whole), 1024 x 1024 8.0 / 29.8 (5 tiles, 4
# whole), 512 x 1024 8.8 / 32.1, 1024 x 512 15.5 / 38.7; 2048 rows either way
# do not fit VMEM. By whole traced steps of the 12-layer cell (9 window
# layers): 1,223.3 ms a step at 512 x 512, 1,153.5 at 1024 x 1024.
WIDE_WINDOW_BLOCK = 1024

# Blocks under grouped queries, where a tile's rows are a kv head's whole
# group x the query block (``ops/attention.py``: the folded kernels). The
# three kernels alone on a v5e, traced (my chip runs, PR 49; q x k blocks;
# forward / dQ / dK-dV ms a call, then a whole forward-and-backward with the
# XLA passes beside the kernels: delta, and unfolded the sum of dK / dV over a
# group). `1 x 72 : 8 x 16384 x 128` under 512 keys (the band of a query block
# as ONE tile unless "walk"):
#   one head a tile, 512 x 512   9.16 / 6.75 / 7.95   26.84  (2.0 x the kept pairs)
#   folded 512 x 512 walk        5.45 / 5.29 / 7.05   18.89  (2.0 x)
#   folded 256 x 512 walk        5.64 / 5.34 / 6.66   18.70
#   folded 256 x 256 walk        7.25 / 4.42 / 5.27   18.02  (1.5 x, three rescales)
#   folded 128 x 128 walk       10.17 / 6.35 / 6.05   23.67  (1.25 x, five)
#   folded 256 x 256, [2304, 768] 3.15 / 3.71 / 5.27  13.20  (1.5 x, no rescale)
#   folded 128 x 128, [1152, 640] 2.63 / 3.31 / 6.06  13.08  (1.25 x)
#   folded 128 x 512, [1152, 640] 2.63 / 3.31 / 6.95  13.96
#   folded 128 x 256, [1152, 640] 2.63 / 3.31 / 5.74  12.74  <- the rule
# `1 x 28 : 4 x 16384 x 128` under 4,096 keys (the band is walked: it fits no
# tile):
#   one head a tile, 1024 x 1024  7.88 / 8.99 / 11.79  30.17
#   folded 1024 x 1024            7.46 / 8.56 / 12.02  28.68
#   folded 512 x 1024             7.55 / 8.61 / 12.04  28.80
#   folded 256 x 1024             7.73 / 8.59 / 11.24  28.20
#   folded 128 x 1024             8.29 / 9.91 / 11.47  30.31
#   folded 512 x 512              7.21 / 8.07 / 10.96  26.82
#   folded 256 x 512              7.78 / 8.17 / 10.43  26.97  <- the rule (half the rows)
#   folded 128 x 512              8.56 / 8.69 / 10.95  28.80
#   folded 256 x 256             13.07 / 8.50 / 10.47  32.60
#   folded 128 x 256             14.25 / 10.81 / 11.54 37.18
# dK/dV wants a key block of 256 or 512 whatever the query block (its matrix
# unit streams the key block's rows); the forward wants the whole band at once.
FOLDED_NARROW_BLOCKS = (128, 256)
FOLDED_WIDE_BLOCKS = (256, 512)


def window_blocks(window: int, rep: int = 1) -> tuple[int, int]:
    """(query rows, key rows) of the ``attn_win_*`` kernels' blocks, by the
    window and by ``rep``, the query heads a kv head. One head a group: square
    tiles, 512-blocks for a window of at most 512 keys (a query block's band
    is two key blocks) and WIDE_WINDOW_BLOCK for a longer one. Grouped queries
    (the kernels fold the group into a tile's rows): a SHORT query block, whose
    band of ``window + 127`` keys is one tile at up to 512 keys (the key rows
    are then dK/dV's block alone), and for a longer window 256 queries against
    key blocks of 512."""
    if rep == 1:
        block = 512 if window <= 512 else WIDE_WINDOW_BLOCK
        return block, block
    return FOLDED_NARROW_BLOCKS if window <= 512 else FOLDED_WIDE_BLOCKS


def gqa_mixer(h, layer, a: GroupedQueryAttention, *, config, positions, mesh=None):
    """h [B, S, E] (normed) -> (y [B, S, E], aux). ``aux`` is ``{}`` for a
    full layer and ``window_share`` for a window layer. Under a ``mesh`` the
    heads shard over tp and the rows over the data axes, as the ``attn``
    kind's do; the sequence is not split (no ``sp`` path)."""
    s = h.shape[1]
    if mesh is not None and mesh.shape["sp"] > 1:
        raise NotImplementedError("grouped-query attention by spec does not split the "
                                  "sequence: the mesh's sp is > 1")
    with device_scope("gqa_win" if a.window else "gqa_full"):
        q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"])
        k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"])
        v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"])
        q = _rope(q, positions, a)
        if mesh is not None:
            q = shard_constraint(q, mesh, ("batch", "heads", "seq", "head_dim"))
        q = checkpoint_name(q, "q")
        k = checkpoint_name(_rope(k, positions, a), "k")
        v = checkpoint_name(v, "v")
        aux = {}
        # a published constant in D^-1/2's place, where the spec states one
        scale = {} if a.softmax_scale is None else {"sm_scale": a.softmax_scale}
        if a.window:
            block_q, block_k = window_blocks(a.window, a.heads // a.kv_heads)
            attn = flash_per_shard(q, k, v, mesh, causal=True, window=a.window,
                                   block_q=block_q, block_k=block_k, **scale)
            kept = jnp.sum(jnp.minimum(positions.astype(jnp.float32) + 1.0, a.window))
            aux["window_share"] = kept / (positions.size / s) / (s * (s + 1) / 2)
        else:
            attn = flash_per_shard(q, k, v, mesh, causal=True, **scale)
        if a.gate == "headwise":
            with device_scope("attn_gate"):
                attn = sigmoid_gate(h, layer["w_attn_gate"], attn)
        return jnp.einsum("bhsd,hde->bse", attn, layer["wo"]), aux


def _matmul_params(a: GroupedQueryAttention, c) -> float:
    gate = a.heads if a.gate == "headwise" else 0
    return c.hidden * (a.head_dim * (2 * a.heads + 2 * a.kv_heads) + gate)


def _mixing_flops(a: GroupedQueryAttention, c, seq: int) -> float:
    """Scores and values, forward, over the keys a query keeps: the causal
    triangle's, or the band's under a window."""
    return 2.0 * a.heads * 2 * a.head_dim * kept_keys(seq, a.window or seq)


def _kind(field: str) -> LayerKind:
    spec = lambda c: getattr(c, field)  # noqa: E731
    return LayerKind(
        axes=lambda c: _axes(spec(c)),
        init=lambda c, keys, lead, normal: _init(spec(c), c, keys, lead, normal),
        apply=lambda h, layer, **kw: gqa_mixer(h, layer, spec(kw["config"]), **kw),
        matmul_params=lambda c: _matmul_params(spec(c), c),
        mixing_flops=lambda c, seq: _mixing_flops(spec(c), c, seq),
        save_names=SAVE_NAMES)


GQA = _kind("gqa")
GQA_WINDOW = _kind("gqa_window")

__all__ = ["GroupedQueryAttention", "ScaledGroupedQueryAttention", "Yarn", "GQA", "GQA_WINDOW", "SAVE_NAMES", "gqa_mixer",
           "window_blocks"]
