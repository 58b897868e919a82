"""Gated DeltaNet token mixer (Qwen3-Next's linear-attention layers; Gated
Delta Networks, arXiv 2412.06464), as ``models/moe.py`` is to the routed MLP.

For the normed input ``h`` of a position, KH key heads and VH value heads
of D features (each key head serves VH/KH value heads):

    q, k, v, z = split(h W_qkvz)              KH*D, KH*D, VH*D, VH*D features
    b, a       = split(h W_ba)                VH each
    [q; k; v]  = silu(causal depthwise conv1d([q; k; v], width 4, no bias))
    beta       = sigmoid(b)
    g          = -exp(A_log) * softplus(a + dt_bias)            float32
    q, k       = l2norm(q) * D^-0.5, l2norm(k)                  per head
    o          = gated_delta_rule(q, k, v, g, beta)             ops/gated_delta.py
    o          = rmsnorm(o; w_norm) * silu(z)                   per head, times w
    y          = o W_out

Named scopes ``gdn_proj``, ``gdn_conv``, ``gdn_scan``, ``gdn_out`` split a
layer on the trace. ``SAVE_NAMES`` is what the backward pass reads and
cannot cheaply remake: the projection's q, k and v as the conv takes them
(its backward needs its input, and the conv, the activation and the norms
are elementwise passes to make again where the projection is a 1.1 TFLOP
product), the gates, the scan's output and ``z``. The rule's chunk-local
half (``gdn_wy_fwd``) runs again, and its backward kernel makes the chunks'
inverses again in VMEM: nothing of a chunk's matrices is saved.

The elementwise passes hold no matrix product, and the transposes between
the projections' token-major ``[B, S, features]`` and the rule's head-major
``[B, VH, S, D]`` end every XLA fusion. On the chip, where a head is whole
lane tiles, ``ops/gdn_elementwise.py`` makes them tile by tile in VMEM and
its ``BlockSpec``s do the transposition: ``conv_heads`` (``gdn_conv_fwd`` /
``gdn_conv_bwd``: conv, SiLU, norms, the heads, each key head written for
every value head it serves) and ``gated_norm`` (``gdn_norm_fwd`` /
``gdn_norm_bwd``), each twice forward (remat) and once backward a layer on
what ``SAVE_NAMES`` keeps. Which path runs is ``_in_vmem``'s to say, from the
backend and the shapes; everywhere else ``causal_conv``, ``_l2norm`` and
``_gated_norm`` below ARE the mixer (the tests plant faults in them by name),
and the kernels' tests hold the two paths equal.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import gdn_elementwise
from ..ops.gated_delta import CHUNK, chunked_jnp, gated_delta_rule
from ..ops.trace_log import note_kernel_trace
from ..tpu import on_tpu
from .kinds import LayerKind

SAVE_NAMES = ("gdn_qkv", "gdn_g", "gdn_beta", "gdn_o", "gdn_z")


def _widths(c):
    d = c.gdn_head_dim
    return c.gdn_key_heads * d, c.gdn_value_heads * d


def gdn_axes(c) -> dict:
    return {
        "w_qkvz": ("embed", "mlp"),
        "w_ba": ("embed", None),
        "conv_w": ("mlp", None),
        "A_log": (None,),
        "dt_bias": (None,),
        "gdn_norm": ("norm",),
        "w_out": ("mlp", "embed"),
    }


def init_gdn(c, keys, lead, normal) -> dict:
    """Weights as the delta rule's published training code starts them:
    ``A_log = log(U(0, 16))`` and ``dt_bias = softplus^-1(dt)`` with ``dt``
    log-uniform in [0.001, 0.1], so a step's decay lies in ~(0.2, 1) and
    state crosses chunk boundaries; the gated norm's weight is 1."""
    kw, vw = _widths(c)
    vh, e = c.gdn_value_heads, c.hidden
    k_qkvz, k_gates, k_conv, k_out = keys
    k_ba, k_a, k_dt = jax.random.split(k_gates, 3)
    a = jax.random.uniform(k_a, lead + (vh,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(k_dt, lead + (vh,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    return {
        "w_qkvz": normal(k_qkvz, lead + (e, 2 * kw + 2 * vw), e),
        "w_ba": normal(k_ba, lead + (e, 2 * vh), e),
        "conv_w": normal(k_conv, lead + (2 * kw + vw, c.gdn_conv), c.gdn_conv),
        "A_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
        "gdn_norm": jnp.ones(lead + (c.gdn_head_dim,), c.dtype),
        "w_out": normal(k_out, lead + (vw, e), vw),
    }


def causal_conv(x, w):
    """Depthwise causal conv over positions: x [B, S, C], w [C, W];
    ``y_t = sum_j w[:, j] x_{t - (W-1) + j}``, positions before 0 zero."""
    width, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s].astype(jnp.float32) * w[:, j].astype(jnp.float32)
               for j in range(width))


def _l2norm(x, eps=1e-6):
    f = x.astype(jnp.float32)
    return f * jax.lax.rsqrt(jnp.sum(f * f, axis=-1, keepdims=True) + eps)


def _gated_norm(o, z, weight, eps):
    """RMSNorm of o over a head's features, times the weight itself (not
    1 + w), times silu(z): o, z [..., D], float32 out."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * weight.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))


def _in_vmem(c, rows: int) -> bool:
    """Whether the elementwise passes run as ``ops/gdn_elementwise.py``'s
    kernels: on the chip, where a head is whole lane tiles, the rows come in
    whole units and the conv is the width they are written for. Elsewhere the
    plain functions above are the mixer."""
    return on_tpu() and gdn_elementwise.fits(c.gdn_head_dim, rows, c.gdn_conv)


def _conv_heads(qkv, conv_w, c):
    """The plain path from the projection's qkv [B, S, C] to the rule's
    q, k, v [B, VH, S, D]: conv, SiLU, the heads, q's and k's norms, each key
    head once for every value head it serves."""
    kw, _ = _widths(c)
    kh, vh, d = c.gdn_key_heads, c.gdn_value_heads, c.gdn_head_dim
    b, s, _ = qkv.shape
    qkv = jax.nn.silu(causal_conv(qkv, conv_w))
    heads = lambda t, n: t.reshape(b, s, n, d).transpose(0, 2, 1, 3)  # noqa: E731
    q = _l2norm(heads(qkv[..., :kw], kh)) * d ** -0.5
    k = _l2norm(heads(qkv[..., kw:2 * kw], kh))
    q = jnp.repeat(q.astype(c.dtype), vh // kh, axis=1)
    k = jnp.repeat(k.astype(c.dtype), vh // kh, axis=1)
    return q, k, heads(qkv[..., 2 * kw:], vh).astype(c.dtype)


def gdn_mixer(h, layer, *, config, positions=None, mesh=None, scan=None,
              return_scan: bool = False):
    """h [B, S, E] (normed) -> y [B, S, E]. ``scan`` swaps the kernels for
    another implementation of the rule (tests; ``chunked_jnp``);
    ``return_scan`` also returns the rule's own operands and output
    (q, k, v, g, beta, o: [B, VH, S, ...]), for a comparison of the scan
    alone."""
    c = config
    kw, vw = _widths(c)
    kh, vh, d = c.gdn_key_heads, c.gdn_value_heads, c.gdn_head_dim
    b, s, _ = h.shape
    with device_scope("gdn_proj"):
        qkvz = jnp.einsum("bse,ef->bsf", h, layer["w_qkvz"])
        ba = jnp.einsum("bse,ef->bsf", h, layer["w_ba"],
                        preferred_element_type=jnp.float32)
        qkv = checkpoint_name(qkvz[..., :2 * kw + vw], "gdn_qkv")
        z = checkpoint_name(qkvz[..., 2 * kw + vw:], "gdn_z")
        beta = jax.nn.sigmoid(ba[..., :vh])
        g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., vh:] + layer["dt_bias"].astype(jnp.float32))
    with device_scope("gdn_conv"):
        if _in_vmem(c, s):
            q, k, v = gdn_elementwise.conv_heads(
                qkv, layer["conv_w"], key_heads=kh, value_heads=vh, out_dtype=c.dtype)
        else:
            note_kernel_trace("gdn_conv", "jnp")
            q, k, v = _conv_heads(qkv, layer["conv_w"], c)
        g = checkpoint_name(g.transpose(0, 2, 1), "gdn_g")
        beta = checkpoint_name(beta.transpose(0, 2, 1), "gdn_beta")
    with device_scope("gdn_scan"):
        o = checkpoint_name((scan or gated_delta_rule)(q, k, v, g, beta), "gdn_o")
    seen = {"q": q, "k": k, "v": v, "g": g, "beta": beta, "o": o}
    with device_scope("gdn_out"):
        if _in_vmem(c, s):
            o = gdn_elementwise.gated_norm(o, z, layer["gdn_norm"], eps=c.norm_eps,
                                           out_dtype=c.dtype)
        else:
            note_kernel_trace("gdn_norm", "jnp")
            o = _gated_norm(o.transpose(0, 2, 1, 3), z.reshape(b, s, vh, d),
                            layer["gdn_norm"], c.norm_eps).astype(c.dtype).reshape(b, s, vw)
        y = jnp.einsum("bsf,fe->bse", o, layer["w_out"])
    return (y, seen) if return_scan else y


def gdn_matmul_params(c) -> float:
    kw, vw = _widths(c)
    return c.hidden * (2 * kw + 2 * vw + 2 * c.gdn_value_heads) + vw * c.hidden


def gdn_mixing_flops(c, seq: int) -> float:
    """Forward FLOPs a token of the gated delta rule ITSELF, per value head:
    the three products a token makes against its [D, D] state (``S^T k``,
    ``k d^T``, ``S^T q``: 3 x 2 D^2); plus the conv. What the chunked form
    adds to that to run on matrix units (the products inside a chunk and the
    inverse, ``ops/gated_delta.py``) is this program's way and no model
    FLOP, as a recomputed block is none."""
    d = c.gdn_head_dim
    kw, vw = _widths(c)
    return c.gdn_value_heads * 3 * 2 * d * d + 2 * c.gdn_conv * (2 * kw + vw)


GDN = LayerKind(axes=gdn_axes, init=init_gdn, apply=gdn_mixer,
                matmul_params=gdn_matmul_params, mixing_flops=gdn_mixing_flops,
                save_names=SAVE_NAMES)

__all__ = ["GDN", "SAVE_NAMES", "chunked_jnp", "causal_conv", "gdn_mixer"]
