"""Plain reference of a dense pre-norm decoder (InternLM2, Mistral): the
published equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, no kernel, no scan, no cache, no batching tricks.

    h   = x + Wo . softmax(rope(Wq n1(x)) rope(Wk n1(x))^T / sqrt(d), causal) Wv n1(x)
    out = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
    n(x) = x / sqrt(mean(x^2) + eps) * g;   logits = n(out_L) Wlm

with grouped-query attention (each kv head serves heads/kv_heads query
heads) and rotary embeddings in the split-halves convention (HF
``rotate_half``), base ``rope_theta``. InternLM2 stores Wq/Wk/Wv fused as
``wqkv``; the mathematics is the same.

Departures: none in the equations. The weights are the program's own
bf16-rounded arrays, read by the names of its parameter tree and upcast
to float32 one layer at a time, so the comparison sees the arithmetic and
not a different draw of weights. Independent of ``ray_tpu``: nothing is
imported from it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [S, H, D] float32, positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(x, layer, *, theta: float, eps: float):
    """One block on x [S, E]; ``layer`` holds this layer's weights."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    h = _norm(x, w["attn_norm"], eps)
    q = _rope(mm("se,ehd->shd", h, w["wq"]), theta)
    k = _rope(mm("se,ehd->shd", h, w["wk"]), theta)
    v = mm("se,ehd->shd", h, w["wv"])
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    x = x + mm("shd,hde->se", mm("hqk,khd->qhd", probs, v), w["wo"])
    h = _norm(x, w["mlp_norm"], eps)
    ff = jax.nn.silu(mm("se,em->sm", h, w["w_gate"])) * mm("se,em->sm", h, w["w_up"])
    return x + mm("sm,me->se", ff, w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps: float):
    return jnp.einsum("se,ev->sv", _norm(x, final_norm.astype(jnp.float32), eps),
                      lm_head.astype(jnp.float32), precision=HIGHEST)


def logits(params, tokens, *, rope_theta: float, norm_eps: float):
    """tokens [S] int32 -> float32 logits [S, vocab]: position i scores
    token i+1 given tokens 0..i."""
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["attn_norm"].shape[0]
    for i in range(n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(x, layer, theta=float(rope_theta), eps=float(norm_eps))
    return _head(x, params["final_norm"], params["lm_head"], eps=float(norm_eps))


def loss_of(lg, tokens):
    """Mean next-token cross entropy of one sequence from its logits."""
    ll = jnp.take_along_axis(jax.nn.log_softmax(lg[:-1], axis=-1),
                             tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(ll)


def loss(params, tokens, *, rope_theta: float, norm_eps: float):
    """Mean next-token cross entropy of one sequence, float32."""
    return loss_of(logits(params, tokens, rope_theta=rope_theta,
                          norm_eps=norm_eps), tokens)


@jax.jit
def position_errors(got, want):
    """How far logits ``got`` [S, vocab] stand from the reference's
    ``want``, position by position: the RMS of the difference over the
    vocabulary as a share of the RMS of ``want`` there. This is the
    comparison that decides ``correct``: a mean loss hides what it shows
    (a dropped layer, weights in fewer bits, one bad position)."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    diff = jnp.sqrt(jnp.mean(jnp.square(got - want), axis=-1))
    return diff / jnp.sqrt(jnp.mean(jnp.square(want), axis=-1))
