"""Lightning attention as a token mixer: linear attention whose state decays
by a constant of the head and the layer (TransNormerLLM, arXiv 2307.14995;
MiniMax-Text-01; MiniCPM-SALA's ``lightning-attn`` layers), as
``models/gdn.py`` is the gated delta rule's.

For the normed input ``h`` of a position, H heads of D features:

    q = rope(norm_D(h W_q));  k = rope(norm_D(h W_k));  v = h W_v
    S_t = lam S_{t-1} + k_t^T v_t;   o_t = D^-1/2 q_t S_t        float32 state
    y   = (norm_HD(o) * sigmoid(h W_g)) W_o

The q/k norms are RMSNorms over a head's features with a learned weight each,
rope turns every feature of a head, the output norm runs over all H D features
of a position with a learned weight, and the gate is element-wise. No
activation on q, k or v.

The decay is no parameter. Head j (1..H) of the layer whose index in the
PUBLISHED stack is l, of ``depth`` layers:

    lam = exp(-s_j (1 - l / (depth - 1) + 1e-5)),   s_j = 2^(-8 j / H)

It is a leaf all the same (``log_decay`` [H] float32, one a layer, beside the
routers' selection bias: no gradient moves it, ``lightning_attention`` hands
back zeros for it), because a scanned stack gives a layer nothing else of its
own: the stack fills it from the layers' indices (``LayerKind.buffers``;
``LlamaConfig.layer_ids`` names the published index of each layer of a cut).

Named scopes ``lightning_proj``, ``lightning_scan``, ``lightning_out`` split a
layer on the trace. ``SAVE_NAMES`` is what the backward pass reads: q, k and v
as the recurrence takes them, its output and the gate's projection; the
states are made again in VMEM (``ops/lightning_attention.py``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import apply_rope, rms_norm
from ..ops.lightning_attention import lightning_attention
from .kinds import LayerKind, norm_over_heads, sigmoid_gate

SAVE_NAMES = ("lightning_q", "lightning_k", "lightning_v", "lightning_o", "attn_gate")


@dataclasses.dataclass(frozen=True)
class LightningAttention:
    """The widths of the lightning-attention layers."""

    heads: int
    head_dim: int
    rope_theta: float
    depth: int                  # layers of the PUBLISHED stack: the decay's layer factor


def log_decays(a: LightningAttention, layer_ids) -> np.ndarray:
    """``log(lam)`` [len(layer_ids), H] float32 for layers of those published
    indices."""
    slopes = 2.0 ** (-8.0 * np.arange(1, a.heads + 1) / a.heads)
    factor = 1.0 - np.asarray(layer_ids, np.float64) / (a.depth - 1) + 1e-5
    return (-slopes[None, :] * factor[:, None]).astype(np.float32)


def _axes(c) -> dict:
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "heads", "head_dim"),
        "wv": ("embed", "heads", "head_dim"),
        "q_norm": ("norm",), "k_norm": ("norm",), "o_norm": ("norm",),
        "w_attn_gate": ("embed", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "log_decay": (None,),
    }


def _init(c, keys, lead, normal) -> dict:
    a = c.lightning
    e, h, d = c.hidden, a.heads, a.head_dim
    return {
        "wq": normal(keys[0], lead + (e, h, d), e),
        "wk": normal(keys[1], lead + (e, h, d), e),
        "wv": normal(keys[2], lead + (e, h, d), e),
        "q_norm": jnp.ones(lead + (d,), c.dtype),
        "k_norm": jnp.ones(lead + (d,), c.dtype),
        "o_norm": jnp.ones(lead + (h * d,), c.dtype),
        "w_attn_gate": normal(jax.random.fold_in(keys[0], 1), lead + (e, h, d), e),
        "wo": normal(keys[3], lead + (h, d, e), h * d),
    }


def _buffers(c, ids) -> dict:
    published = [c.layer_ids[i] if c.layer_ids else i for i in ids]
    return {"log_decay": jnp.asarray(log_decays(c.lightning, published))}


def lightning_mixer(h, layer, *, config, positions, mesh=None, scan=None,
                    return_selection: bool = False):
    """h [B, S, E] (normed) -> y [B, S, E]. ``scan`` swaps the kernels for
    another implementation of the recurrence (tests; ``lightning_scan``);
    ``return_selection`` is the block's question to every mixer of a stack
    that selects keys somewhere: this one has no selection to return."""
    c, a = config, config.lightning
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("lightning attention runs on one device: its kernels "
                                  "have no per-shard call yet")
    with device_scope("lightning_proj"):
        q = jnp.einsum("bse,ehd->bhsd", h, layer["wq"])
        k = jnp.einsum("bse,ehd->bhsd", h, layer["wk"])
        v = jnp.einsum("bse,ehd->bhsd", h, layer["wv"])
        q = apply_rope(rms_norm(q, layer["q_norm"], eps=c.norm_eps), positions,
                       theta=a.rope_theta)
        k = apply_rope(rms_norm(k, layer["k_norm"], eps=c.norm_eps), positions,
                       theta=a.rope_theta)
        q, k, v = (checkpoint_name(t, f"lightning_{n}") for t, n in ((q, "q"), (k, "k"), (v, "v")))
    with device_scope("lightning_scan"):
        o = (scan or lightning_attention)(q, k, v, layer["log_decay"],
                                          scale=a.head_dim ** -0.5)
        o = checkpoint_name(o.astype(c.dtype), "lightning_o")
    with device_scope("lightning_out"):
        o = norm_over_heads(o, layer["o_norm"], c.norm_eps)
        o = sigmoid_gate(h, layer["w_attn_gate"], o)
        return jnp.einsum("bhsd,hde->bse", o, layer["wo"])


def _matmul_params(c) -> float:
    a = c.lightning
    return 5.0 * c.hidden * a.heads * a.head_dim       # q, k, v, the gate, the output


def _mixing_flops(c, seq: int) -> float:
    """Forward FLOPs a token of the recurrence ITSELF: the two products a
    token makes against a head's [D, D] state (``k^T v`` into it, ``q S`` out
    of it: 2 x 2 D^2). The products inside a chunk are the program's way to
    run it on matrix units, and no model FLOP."""
    a = c.lightning
    return a.heads * 2 * 2.0 * a.head_dim * a.head_dim


LIGHTNING = LayerKind(axes=_axes, init=_init, apply=lightning_mixer,
                      matmul_params=_matmul_params, mixing_flops=_mixing_flops,
                      save_names=SAVE_NAMES, buffers=_buffers)

__all__ = ["LIGHTNING", "LightningAttention", "SAVE_NAMES", "lightning_mixer", "log_decays"]
