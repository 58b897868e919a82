"""Plain reference of a pre-norm decoder with a routed expert MLP (OLMoE;
Mixtral's rule for the gates is one flag away): the published equations in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision,
no sort, no kernel, no dispatch: EVERY expert is applied to every token and
the result is masked by the top-k choice.

    h = n1(x);  q = nq(h Wq), k = nk(h Wk)  over all heads*head_dim features
                (``qk_norm``; OLMoE), v = h Wv
    x = x + Wo . softmax(rope(q) rope(k)^T / sqrt(d), causal) v
    h = n2(x);  p = softmax(h W_router) over all X experts
    (g, e) = top_k(p);  g = g / sum(g) only if ``norm_topk`` (OLMoE: not)
    x = x + sum_j g_j * Wdown[e_j] (silu(Wgate[e_j] h) * Wup[e_j] h)
    n(x) = x / sqrt(mean(x^2) + eps) * w;   logits = n(x_L) Wlm

    load_balance_l = X * sum_x (rows_lx / (N k)) * mean_n p_l[n, x]
    z_l            = mean_n logsumexp(h W_router)^2
    loss = ce + aux_weight * mean_l load_balance_l + z_weight * mean_l z_l

over the N tokens of all the sequences given (the program takes the same
means over its batch). Rotary embeddings, the norm and the output head are
``dense_decoder``'s. The weights are the program's own bf16-rounded arrays,
read by the names of its parameter tree and upcast to float32, an expert at
a time. Independent of ``ray_tpu``: nothing is imported from it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense_decoder import HIGHEST, _head, _norm, _rope, loss_of, position_errors

__all__ = ["logits", "loss", "aux_losses", "expert_layer", "loss_of",
           "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)


def _route(h, router, *, top_k: int, norm_topk: bool):
    """h [S, E] -> per-expert weights [S, X] (a token's gate where the
    expert was chosen, else 0) and the routing's record."""
    router_logits = mm("se,ex->sx", h, router.astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32)  # [S, k, X]
    weights = jnp.einsum("sk,skx->sx", gates, picked)
    return weights, {"probs": probs, "chosen": chosen,
                     "lse": jax.nn.logsumexp(router_logits, axis=-1)}


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk"))
def expert_layer(h, layer, *, top_k: int, norm_topk: bool):
    """The routed MLP alone on h [S, E] (already normed): (y [S, E], routing).
    Every expert runs on every token; ``weights`` zeroes the unchosen."""
    h = h.astype(jnp.float32)
    weights, routing = _route(h, layer["router"], top_k=top_k, norm_topk=norm_topk)

    def one_expert(y, xs):
        w_gate, w_up, w_down, weight = xs
        ff = (jax.nn.silu(mm("se,em->sm", h, w_gate.astype(jnp.float32)))
              * mm("se,em->sm", h, w_up.astype(jnp.float32)))
        return y + weight[:, None] * mm("sm,me->se", ff, w_down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (layer["w_gate"], layer["w_up"], layer["w_down"], weights.T))
    return y, routing


@functools.partial(jax.jit, static_argnames=("theta", "eps", "top_k", "norm_topk"))
def _layer(x, layer, *, theta: float, eps: float, top_k: int, norm_topk: bool):
    """One block on x [S, E]; ``layer`` holds this layer's weights (``q_norm``
    and ``k_norm`` among them exactly when the model normalises q and k)."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = _norm(x, f32("attn_norm"), eps)
    q = mm("se,ehd->shd", h, f32("wq"))
    k = mm("se,ehd->shd", h, f32("wk"))
    v = mm("se,ehd->shd", h, f32("wv"))
    if "q_norm" in layer:
        q = _norm(q.reshape(q.shape[0], -1), f32("q_norm"), eps).reshape(q.shape)
        k = _norm(k.reshape(k.shape[0], -1), f32("k_norm"), eps).reshape(k.shape)
    q, k = _rope(q, theta), _rope(k, theta)
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    x = x + mm("shd,hde->se", mm("hqk,khd->qhd", probs, v), f32("wo"))
    y, routing = expert_layer(_norm(x, f32("mlp_norm"), eps), layer,
                              top_k=top_k, norm_topk=norm_topk)
    return x + y, routing


def logits(params, tokens, *, rope_theta: float, norm_eps: float, top_k: int,
           norm_topk: bool):
    """tokens [S] int32 -> (float32 logits [S, vocab], routing): position i
    scores token i+1 given tokens 0..i; ``routing`` holds ``probs``
    [L, S, X], ``chosen`` [L, S, k] and ``lse`` [L, S]."""
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["attn_norm"].shape[0]
    routings = []
    for i in range(n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x, routing = _layer(x, layer, theta=float(rope_theta), eps=float(norm_eps),
                            top_k=top_k, norm_topk=norm_topk)
        routings.append(routing)
    out = _head(x, params["final_norm"], params["lm_head"], eps=float(norm_eps))
    return out, jax.tree.map(lambda *a: jnp.stack(a), *routings)


def aux_losses(routings):
    """(load_balance, z), each the mean over layers, over the tokens of all
    the sequences whose ``routing`` is in the list."""
    probs = jnp.concatenate([r["probs"] for r in routings], axis=1)    # [L, N, X]
    chosen = jnp.concatenate([r["chosen"] for r in routings], axis=1)  # [L, N, k]
    lse = jnp.concatenate([r["lse"] for r in routings], axis=1)
    n_experts, top_k = probs.shape[-1], chosen.shape[-1]
    rows = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32), axis=(1, 2))
    frac = rows / (probs.shape[1] * top_k)                             # [L, X]
    balance = n_experts * jnp.sum(frac * jnp.mean(probs, axis=1), axis=-1)
    return jnp.mean(balance), jnp.mean(jnp.square(lse))


def loss(params, rows, *, aux_weight: float, z_weight: float, **arch):
    """The training loss of token rows [B, S]: mean next-token cross entropy
    over all rows plus the weighted auxiliary terms."""
    ces, routings = [], []
    for row in rows:
        lg, routing = logits(params, row, **arch)
        ces.append(loss_of(lg, row))
        routings.append(routing)
    balance, z = aux_losses(routings)
    return jnp.mean(jnp.stack(ces)) + aux_weight * balance + z_weight * z
