"""A double-gated short convolution as a token mixer (the ``conv`` layers of
LFM2), as ``models/mamba2.py`` is the state-space layer's: here the causal conv
IS the mixer, and nothing else mixes tokens in such a layer.

For the normed input ``h`` of a position, E features, K taps:

    [B | C | X] = h W_in                       W_in [E, 3 E], no bias, the thirds in THAT order
    u   = B * X
    v_t = sum_i k_i u_{t - (K - 1) + i}        depthwise (a channel its own K taps), causal,
                                               zeros before the row, no bias; k_{K-1} meets t
    out = (C * v) W_out                        W_out [E, E], no bias

No activation and no norm inside the mixer; rows of a batch do not see each
other. The published class holds ``W_in`` as one matrix; the leaf ``w_in`` is
that matrix as [E, 3, E], the same numbers, so that the product leaves its
thirds as whole arrays [3, B, S, E] and no third is a slice at a lane offset
of another's rows. ``conv`` is [K, E].

The two gates and the conv run in float32 and round once under ``sconv_mix``:
on the chip, at shapes ``ops/sconv_elementwise.py::fits`` takes (features in
whole lane tiles, rows in whole units of 64, three taps, the model's own float
dtype), as that module's kernel pair ``sconv_fwd`` / ``sconv_bwd``, which keeps
every float32 intermediate in VMEM and reads the thirds where the product left
them; on every other backend and at every other width as ``gated_conv`` in
plain XLA (``trace_log.kernel_traces()`` says which: ``sconv:pallas`` or
``sconv:jnp``). The products on either side are ``sconv_proj`` and
``sconv_out``. ``SAVE_NAMES`` is what remat ``attn`` keeps of a layer: the
stream as the mixer's output joins it (``kinds.POST_ATTN``), so that the
block's second run makes no ``W_out`` product; B, C and X ([3, tokens, E]:
three times that) are made again from the block's input, one product, and the
backward pass reads them there. The mixer counts ``past_share`` beside its
output: ``|v - k_{K-1} u|^2 / |v|^2``, the share of the conv's output that
comes from EARLIER positions; 0 if those taps do nothing, (K - 1) / K under
seeded taps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability.tracing import device_scope
from ..ops import sconv_elementwise
from ..ops.trace_log import note_kernel_trace
from ..tpu import on_tpu
from .kinds import POST_ATTN, LayerKind

SAVE_NAMES = (POST_ATTN,)


def _axes(c) -> dict:
    return {"w_in": ("embed", None, "mlp"), "conv": (None, "mlp"), "w_out": ("mlp", "embed")}


def _init(c, keys, lead, normal) -> dict:
    e, k = c.hidden, c.sconv_taps
    return {"w_in": normal(keys[0], lead + (e, 3, e), e),
            # the conv's fan-in is its K taps, as ``models/mamba2.py``'s
            "conv": normal(keys[1], lead + (k, e), k),
            "w_out": normal(keys[2], lead + (e, e), e)}


def gated_conv(b, c, x, taps):
    """b, c, x [B, S, E], taps [K, E] -> (``c * conv(b * x)`` float32 [B, S, E],
    ``past_share``). The conv: ``v_t = sum_i taps[i] u_{t - (K - 1) + i}``,
    zeros before the row."""
    k, s = taps.shape[0], x.shape[1]
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    u = jnp.pad(f32(b) * f32(x), ((0, 0), (k - 1, 0), (0, 0)))
    taps = f32(taps)
    past = sum(taps[i] * u[:, i:i + s] for i in range(k - 1))
    v = past + taps[k - 1] * u[:, k - 1:]
    share = jnp.sum(jnp.square(past)) / jnp.maximum(jnp.sum(jnp.square(v)), 1e-30)
    return f32(c) * v, jax.lax.stop_gradient(share)


def sconv_mixer(h, layer, *, config, positions, mesh=None, return_selection: bool = False):
    """h [B, S, E] (normed) -> (y [B, S, E], {"past_share"}).
    ``return_selection`` is the block's question to every mixer of a stack that
    selects keys somewhere: this one has no selection to return."""
    with device_scope("sconv_proj"):
        bcx = jnp.einsum("bse,egc->gbsc", h, layer["w_in"])
    with device_scope("sconv_mix"):
        taps = layer["conv"]
        if on_tpu() and sconv_elementwise.fits(bcx.shape[3], bcx.shape[2], taps.shape[0],
                                               bcx.dtype):
            gated, past_share = sconv_elementwise.gated_conv3(bcx, taps)
        else:
            note_kernel_trace("sconv", "jnp")
            gated, past_share = gated_conv(*bcx, taps)
            gated = gated.astype(h.dtype)
    with device_scope("sconv_out"):
        out = jnp.einsum("bsc,ce->bse", gated, layer["w_out"])
    return out, {"past_share": past_share}


SCONV = LayerKind(axes=_axes, init=_init, apply=sconv_mixer,
                  matmul_params=lambda c: 4.0 * c.hidden * c.hidden,
                  save_names=SAVE_NAMES)

__all__ = ["SCONV", "SAVE_NAMES", "gated_conv", "sconv_mixer"]
