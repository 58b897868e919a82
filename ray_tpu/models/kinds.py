"""What a kind of layer part owns.

A decoder block is ``x += mixer(norm(x)); x += mlp(norm(x))``, and an MLP
kind may besides compute something from the block's input ``x`` itself,
before the mixer runs (``early``: a router that reads the residual stream as
it enters the block; most kinds have none). The stack
(``models/llama.py``) knows that much and no more: which leaves a mixer or
an MLP has, how they shard, how they start, what they cost, what of their
forward pass a remat policy may save and what they count beside the loss
belong to the kind, so a new kind is a new ``LayerKind`` and no new branch
in the stack.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import flash_attention
from ..ops.rope import yarn_frequencies

# The one checkpoint name that is the stack's and not a kind's: the residual
# stream as the mixer's output joins it (``models/llama.py::_block`` gives
# it). A mixer kind lists it in ``save_names`` to keep the stream under remat
# ``attn``, and the block's second run then makes neither the mixer's output
# product nor the add (the latent kinds ``mla`` and ``mla_win``: models/mla.py;
# the grouped-query kinds ``gqa`` and ``gqa_win``: models/gqa.py; the short
# conv: models/short_conv.py).
POST_ATTN = "post_attn"


@dataclasses.dataclass(frozen=True)
class LayerKind:
    # config -> {leaf: logical axes of ONE layer's leaf}
    axes: Callable[[Any], dict]
    # (config, keys, lead, normal) -> {leaf: array}, every leaf with ``lead``
    # prepended to its shape (the layers stacked for the scan); ``keys`` are
    # the kind's own PRNG keys and ``normal(key, shape, fan_in)`` the
    # stack's truncated-normal draw in the model's dtype
    init: Callable[[Any, Any, tuple, Callable], dict]
    # mixer: (h, layer, config=, positions=, mesh=) -> y
    # mlp:   (h, layer, config=, mesh=, ep_axis=) -> (y, aux), and with
    #        ``early=`` what its ``early`` returned, where that is not None
    apply: Callable
    # config -> matmul parameters one token passes through in one layer
    matmul_params: Callable[[Any], float]
    # (config, seq) -> forward FLOPs a token of one layer that are no
    # parameter product (attention's scores, a scan's state); x3 trained
    mixing_flops: Callable[[Any, int], float] = lambda c, seq: 0.0
    # checkpoint names the ``attn`` remat policy saves for this kind
    save_names: tuple[str, ...] = ()
    # (config, ids) -> {leaf: array}: the kind's leaves that no gradient moves
    # and that depend on WHICH layers of the stack these are (``ids``: the
    # stack's index of each of the leaf's stacked layers, or of the one
    # leading layer); they have their entry in ``axes`` like any leaf
    buffers: Callable | None = None
    # mlp only: (x, layer, config=) -> what the kind computes from the
    # block's input x [B, S, E] (before the attention norm and the mixer), or
    # None; the block hands it to ``apply`` as ``early=``
    early: Callable | None = None


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's published parameters (a ``rope_parameters`` / ``rope_scaling``
    group of type ``yarn``)."""

    factor: float
    original_length: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rope_keywords(rotated: int, theta: float, yarn: Yarn | None) -> dict:
    """``ops.apply_rope``'s keywords for the frequencies a kind's spec states
    over its ``rotated`` features: ``theta``'s plain ones, or under ``yarn``
    YaRN's blended ones with its ``attention_factor`` on cos and sin."""
    if yarn is None:
        return {"theta": theta}
    return {"inv_freq": yarn_frequencies(
        rotated, theta=theta, factor=yarn.factor, original_length=yarn.original_length,
        beta_fast=yarn.beta_fast, beta_slow=yarn.beta_slow), "factor": yarn.attention_factor}


def sigmoid_gate(h, w_gate, attn):
    """attn [B, H, S, D] times ``sigmoid(h W_g)``, in float32, by the gate's
    shape. ``w_gate`` [E, H]: one number a head and position (the head-wise
    form of arXiv 2505.06708), the gate itself under the checkpoint name
    ``attn_gate``. ``w_gate`` [E, H, D]: one a feature (the element-wise form);
    there the projection goes under that name as the product leaves it, and
    the sigmoid is made again from it. h [B, S, E] is the mixer's normed
    input."""
    if w_gate.ndim == 3:
        gate = checkpoint_name(jnp.einsum("bse,ehd->bhsd", h, w_gate), "attn_gate")
        return (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)
    gate = checkpoint_name(jax.nn.sigmoid(jnp.einsum(
        "bse,eh->bhs", h, w_gate, preferred_element_type=jnp.float32)), "attn_gate")
    return (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)


def norm_over_heads(t, weight, eps):
    """RMSNorm of t [B, H, S, D] over all H*D features of a position (the
    whole projection, as OLMoE normalises q and k), weight [H*D]; f32
    statistics."""
    _, h, _, d = t.shape
    f = t.astype(jnp.float32)
    var = jnp.mean(jnp.square(f), axis=(1, 3), keepdims=True)
    w = weight.astype(jnp.float32).reshape(1, h, 1, d)
    return (f * jax.lax.rsqrt(var + eps) * w).astype(t.dtype)


def kept_keys(seq: int, width: int) -> float:
    """Mean keys a query attends when it keeps at most ``width`` of its
    causal keys."""
    width = min(width, seq)
    return (width * (width + 1) / 2 + (seq - width) * width) / seq


def flash_per_shard(q, k, v, mesh, **kw):
    """``flash_attention(q, k, v, **kw)`` on [B, H, S, D]. The compiler cannot
    partition a Mosaic kernel by itself ("wrap the call in a shard_map"), so
    under a mesh of more than one device it runs per shard, batch rows over
    the data axes and kv-head groups over tp: each is independent in
    attention, so nothing is exchanged. Where the batch or a head count does
    not divide, the call is left whole."""
    batch_axes = ("dcn", "dp", "fsdp")
    if mesh is not None and mesh.size > 1:
        n_batch = math.prod(mesh.shape[a] for a in batch_axes)
        tp = mesh.shape["tp"]
        if not (q.shape[0] % n_batch or q.shape[1] % tp or k.shape[1] % tp):
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            spec = P(batch_axes, "tp", None, None)
            return shard_map(
                functools.partial(flash_attention, **kw),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)
    return flash_attention(q, k, v, **kw)
