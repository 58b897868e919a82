"""Plain reference of a latent-attention decoder that attends EVERY causal key
(Kimi-K2-Instruct, from its ``config.json``; the mechanisms as DeepSeek-V2
published latent attention and DeepSeek-V3 YaRN's softmax factor, the sigmoid
router with its selection bias, the routed scale and the sequence-wise balance
term). Straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernel, no sort of rows, no dispatch, no scan over layers: every
query scores every key and what it may not see is masked; EVERY expert held
here is applied to every token and masked by the top-k choice. Blocks over
queries, groups of heads and rows of the dense MLP only so that 8,192 x 8,192
x 64 scores never stand whole.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. A block: ``x += mixer(n(x));
x += mlp(n(x))``. The leading layers have a dense SwiGLU, the others the
expert layer. No bias anywhere, no gate, no rescale.

The mixer (ranks r_q, r_kv; H heads; d_n + d_r query and key features, d_v
value features; ``h`` the normed input):

    c_q = n(h W_dq);  q_j = c_q W_uq[j], rope on its last d_r
    [c_kv | k_r] = h W_dkv;  c_kv = n(c_kv);  k_r = rope(k_r), one for all heads
    [k_j^n | v_j] = c_kv W_ukv[j];  k_j = [k_j^n | k_r]
    o_j[t] = sum over s <= t of softmax_s(f q_j[t] . k_j[s] (d_n + d_r)^-1/2) v_j[s]
    y = concat_j(o_j) W_o

Rope turns pair i of d_r / 2 (split halves) by ``f_i = theta^(-2i/d_r)`` or,
under YaRN (``yarn``: ``factor`` F, ``original_length`` L, ``beta_fast``,
``beta_slow``, ``attention_factor`` A), by

    dim(r)     = d_r ln(L / (2 pi r)) / (2 ln theta)
    lo, hi     = max(floor(dim(beta_fast)), 0), min(ceil(dim(beta_slow)), d_r - 1)
    m_i        = 1 - clip((i - lo) / max(hi - lo, 0.001), 0, 1)
    inv_freq_i = (f_i / F)(1 - m_i) + f_i m_i

with cos and sin multiplied by A and the softmax scale by ``softmax_factor``
f. In latent attention's published form ``A = mscale(F, mscale) / mscale(F,
mscale_all_dim)`` and ``f = mscale(F, mscale_all_dim)^2``, ``mscale(F, m) =
0.1 m ln F + 1`` (``yarn_factors``). The published numbers (d_r 64, theta
50,000, F 32, L 4,096, both betas 1, both mscales 1): dim(1) = 19.16, lo 19,
hi 20, pairs 0-19 kept, 20-31 slowed 32-fold; A = 1, f = 1.81326.

Expert layer: ``s = sigmoid(h W_r)`` over all X experts; the k experts of
largest ``s + b``; gates ``scale s[e] / sum s[e]``; ``y = sum_j g_j
E_{e_j}(h)`` over the chosen experts AMONG THOSE HELD (a chip's share; what
absent experts would add is left out, here as in the program) ``+
E_shared(h)``, every expert a SwiGLU. The balance term, a sequence at a time
over all X experts: ``sum_x (X rows_x / (k S)) mean_t (s / sum_x s)[t, x]``,
the counts constants.

The loss of rows [B, S]: mean next-token cross entropy + ``aux_weight`` x the
balance term (mean over rows and expert layers). ``loss_and_grads`` makes the
same loss and its gradient a block at a time, so that 2.8 B parameters'
float32 copies never exist together. ``bias_after`` is the selection bias's
own step. The weights are the program's own arrays read by the names of its
parameter tree (``lead_layers/layer<i>/<leaf>``; ``layers/<leaf>`` stacked
over the periods of one layer) and upcast to float32. Independent of
``ray_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, loss_of, position_errors
from .latent_sparse_decoder import _norm, _swiglu, bias_after, dense_mlp, embed, head

__all__ = ["logits", "loss", "loss_and_grads", "block", "layers_of", "embed", "head",
           "mla_mixer", "expert_layer", "dense_mlp", "yarn_inv_freq", "yarn_factors",
           "bias_after", "loss_of", "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)
# Query rows scored at a time, for one group of heads: 16 heads x 128 rows x
# 8,192 keys are 67 MB of float32 scores. Each block is the plain softmax over
# all its keys, recomputed in a backward pass.
QUERY_BLOCK = 128
# Heads whose q, k and v exist at a time: all 64 are 1.1 GB of float32 a row
# of 8,192, and as much again for their cotangents.
HEAD_GROUP = 16


def yarn_factors(factor: float, mscale: float, mscale_all_dim: float) -> tuple[float, float]:
    """(A, f) of the module's text from a published ``rope_scaling`` group."""
    of = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0  # noqa: E731
    return of(mscale) / of(mscale_all_dim), of(mscale_all_dim) ** 2


def yarn_inv_freq(d: int, theta: float, factor: float, original_length: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's d / 2 inverse frequencies (the module's text), float32."""
    f = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    dim = lambda r: d * math.log(original_length / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    lo, hi = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), d - 1)
    m = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3),
                       0.0, 1.0)
    return (f / factor) * (1.0 - m) + f * m


def _rope(x, spec: dict):
    """x [S, H, D]: rotate the LAST ``rope_dim`` features (their own split
    halves), positions 0..S-1."""
    s, d = x.shape[0], spec["rope_dim"]
    yarn = spec.get("yarn")
    if yarn:
        inv = yarn_inv_freq(d, spec["rope_theta"], yarn["factor"], yarn["original_length"],
                            yarn["beta_fast"], yarn["beta_slow"])
    else:
        inv = 1.0 / spec["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    scale = yarn["attention_factor"] if yarn else 1.0
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    first = x.shape[-1] - d
    rest, x1, x2 = x[..., :first], x[..., first:first + d // 2], x[..., first + d // 2:]
    return jnp.concatenate([rest, x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla_mixer(h, layer, spec: dict, eps: float):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``q_rank``,
    ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim``, ``rope_theta``,
    ``yarn`` (a dict or None), ``softmax_factor``.

    So that it fits: the heads are taken HEAD_GROUP at a time (a group's q, k
    and v are made from the latents where they are used and made again in a
    backward pass) and the queries QUERY_BLOCK at a time. Every query still
    scores every key, in float32."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, d_n, d_r, d_v = spec["heads"], spec["nope_dim"], spec["rope_dim"], spec["v_dim"]
    r_kv = spec["kv_rank"]
    c_q = _norm(mm("se,er->sr", h, f32("w_dq")), f32("q_a_norm"), eps)
    down = mm("se,er->sr", h, f32("w_dkv"))
    c_kv = _norm(down[:, :r_kv], f32("kv_a_norm"), eps)
    k_r = _rope(down[:, None, r_kv:], spec)[:, 0]                          # [S, d_r]
    scale = spec["softmax_factor"] / math.sqrt(d_n + d_r)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    n_groups = heads // group
    by_group = lambda w: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], n_groups, group, w.shape[2]), 1, 0)
    keys = jnp.arange(s)

    @jax.checkpoint
    def attend(w_q, w_kv):
        """One group of heads: [S, group, d_v]."""
        q = _rope(mm("sr,rhd->shd", c_q, w_q), spec)
        kv = mm("sr,rhd->shd", c_kv, w_kv)
        k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(k_r[:, None], (s, group, d_r))],
                            axis=-1)
        v = kv[..., d_n:]

        @jax.checkpoint
        def rows(q_rows, first):
            scores = mm("qhd,khd->hqk", q_rows, k) * scale
            causal = (first + jnp.arange(block))[:, None] >= keys[None, :]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return mm("hqk,khd->qhd", probs, v)

        out = jax.lax.map(lambda xs: rows(*xs), (q.reshape(s // block, block, group, d_n + d_r),
                                                 jnp.arange(0, s, block)))
        return out.reshape(s, group, d_v)

    attn = jax.lax.map(lambda xs: attend(*xs), (by_group(f32("w_uq")), by_group(f32("w_ukv"))))
    attn = jnp.moveaxis(attn, 0, 1).reshape(s, heads, d_v)
    return mm("shd,hde->se", attn, f32("wo"))


def expert_layer(h, layer, *, top_k: int, norm_topk: bool, first: int = 0,
                 scale: float = 1.0):
    """The expert layer alone on h [S, E] (normed): (y [S, E], routing). The
    router scores all X experts; the ``count`` experts whose weights ``layer``
    holds are experts ``first .. first + count - 1``, each applied to every
    token and weighted by the token's gate for it (0 where it was not chosen);
    the shared expert is added plain. ``routing``: ``probs`` (the sigmoid
    scores), ``biased`` (what the choice ranks), ``chosen``, ``rows`` [X]
    (constants) and ``share_mean`` [X] (what the balance term takes of this
    sequence: the mean over its tokens of ``s / sum_x s``)."""
    h = h.astype(jnp.float32)
    scores = jax.nn.sigmoid(mm("se,ex->sx", h, layer["router"].astype(jnp.float32)))
    biased = scores + jax.lax.stop_gradient(layer["router_bias"].astype(jnp.float32))
    chosen = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)[1]
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * scale
    n_experts = scores.shape[-1]
    picked = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)          # [S, k, X]
    weights = jnp.einsum("sk,skx->sx", gates, picked)
    count = layer["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    # an expert's weighted part is recomputed in a backward pass
    @jax.checkpoint
    def part(w_gate, w_up, w_down, weight):
        return weight[:, None] * _swiglu(h, w_gate, w_up, w_down)

    y, _ = jax.lax.scan(lambda y, xs: (y + part(*xs), None), jnp.zeros_like(h),
                        (layer["w_gate"], layer["w_up"], layer["w_down"], held.T))
    y = y + _swiglu(h, layer["w_shared_gate"], layer["w_shared_up"], layer["w_shared_down"])
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return y, {"probs": scores, "biased": biased, "chosen": chosen,
               "rows": jax.lax.stop_gradient(jnp.sum(picked, axis=(0, 1))),
               "share_mean": jnp.mean(share, axis=0)}


def _balance(rows, share_mean, n_tokens: int, top_k: int):
    """One sequence's balance term of one layer (or, [..., X], of several):
    ``sum_x (X rows_x / (k S)) share_mean_x``."""
    n_experts = rows.shape[-1]
    return jnp.sum(n_experts * rows / (n_tokens * top_k) * share_mean, axis=-1)


def block(x, layer, lead: bool, arch: dict):
    """One decoder block on x [S, E] float32: (x, the expert layer's
    ``routing``, or ``{}`` for a leading layer's dense MLP)."""
    eps = arch["norm_eps"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    x = x + mla_mixer(h, layer, arch["spec"], eps)
    h = _norm(x, layer["mlp_norm"].astype(jnp.float32), eps)
    if lead:
        return x + dense_mlp(h, layer), {}
    y, routing = expert_layer(h, layer, top_k=arch["top_k"], norm_topk=arch["norm_topk"],
                              first=arch["held_first"], scale=arch["routed_scale"])
    return x + y, routing


def layers_of(params, arch: dict) -> list:
    """The model's blocks in order: (the block's leaves, whether it is a
    leading layer, where its leaves lie in ``params``: (``lead_layers``,
    ``layer<i>``) or (``layers``, period))."""
    layers = [(params["lead_layers"][f"layer{i}"], True, ("lead_layers", f"layer{i}"))
              for i in range(arch["lead_layers"])]
    stacked = params["layers"]
    for p in range(stacked["attn_norm"].shape[0]):
        layers.append((jax.tree.map(lambda a: a[p], stacked), False, ("layers", p)))
    return layers


def logits(params, tokens, arch: dict):
    """tokens [S] int32 -> (float32 logits [S, vocab], routing stacked over
    the expert layers). ``arch``: ``spec`` (the mixer's), ``lead_layers``,
    ``norm_eps``, ``top_k``, ``norm_topk``, ``held_first``, ``routed_scale``."""
    x = embed(params["embed"], tokens)
    routings = []
    for layer, lead, _ in layers_of(params, arch):
        # a block is recomputed in a backward pass (its input alone is kept)
        x, routing = jax.checkpoint(
            lambda x, layer, lead=lead: block(x, layer, lead, arch))(x, layer)
        if routing:
            routings.append(routing)
    out = head(x, params["final_norm"], params["lm_head"], arch["norm_eps"])
    return out, jax.tree.map(lambda *a: jnp.stack(a), *routings)


def loss(params, rows, arch: dict, *, aux_weight: float, return_seen: bool = False):
    """The training loss of token rows [B, S] (the module's text).
    ``return_seen=True`` returns ``(loss, seen)`` for ``value_and_grad(
    has_aux=True)``: the first row's ``logits``, router ``probs`` and
    ``biased``, the two terms (``ce``, ``balance``) and ``rows_per_expert``
    [expert layers, X] over all rows."""
    rows = jnp.asarray(rows)

    # a row at a time, recomputed whole in a backward pass
    def one(row):
        lg, routing = logits(params, row, arch)
        return loss_of(lg, row), routing, lg

    ces, routing, lgs = jax.lax.map(jax.checkpoint(one), rows)
    ce = jnp.mean(ces)
    balance = jnp.mean(_balance(routing["rows"], routing["share_mean"], rows.shape[1],
                                arch["top_k"]))
    total = ce + aux_weight * balance
    if not return_seen:
        return total
    return total, {"logits": lgs[0], "probs": routing["probs"][0],
                   "biased": routing["biased"][0], "ce": ce, "balance": balance,
                   "rows_per_expert": jnp.sum(routing["rows"], axis=0)}


def loss_and_grads(params, rows, arch: dict, *, aux_weight: float) -> tuple:
    """``loss`` and its gradient, a BLOCK at a time and by hand: (loss, seen,
    {leaf path as ``jax.tree_util.keystr`` prints it: the gradient in the
    leaf's own type}). ``seen`` as ``loss``'s, the first row's logits, scores
    and biased scores on the host.

    A row at a time: a forward pass keeps each block's input, the head gives
    the gradient of its own leaves and of the last hidden state, and each
    block's own ``jax.vjp`` is run under one ``jit`` a kind of block, the
    balance term entering it by the row's ``share_mean`` against that row's
    own counts (the term is taken a sequence at a time, so no first pass over
    the other rows is needed). One block's float32 weights, their cotangents
    and its activations are on the device at a time. The same numbers as
    ``jax.grad`` of ``loss`` (a test holds them equal)."""
    rows = jnp.asarray(rows)
    blocks = [(lead, where) for _, lead, where in layers_of(params, arch)]
    n_rows, seq = rows.shape
    n_expert_layers = sum(not lead for lead, _ in blocks)
    top_k = arch["top_k"]

    def leaves(where):
        if where[0] == "lead_layers":
            return params[where[0]][where[1]]
        return jax.tree.map(lambda a: a[where[1]], params["layers"])

    @functools.lru_cache(maxsize=None)
    def forward(lead):
        return jax.jit(lambda x, layer: block(x, layer, lead, arch))

    @functools.lru_cache(maxsize=None)
    def backward(lead):
        def pull(x, layer, ct):
            def terms(x, layer):
                y, routing = block(x, layer, lead, arch)
                if not routing:
                    return y, jnp.zeros((), jnp.float32)
                # this row's and this layer's part of aux_weight x balance
                return y, (aux_weight / (n_expert_layers * n_rows)
                           * _balance(routing["rows"], routing["share_mean"], seq, top_k))

            return jax.vjp(terms, x, layer)[1]((ct, jnp.ones((), jnp.float32)))

        return jax.jit(pull)

    @jax.jit
    def head_terms(x, final_norm, lm_head, row):
        def ce_of(x, final_norm, lm_head):
            lg = head(x, final_norm, lm_head, arch["norm_eps"])
            return loss_of(lg, row) / n_rows, lg

        (ce, lg), grads = jax.value_and_grad(ce_of, argnums=(0, 1, 2), has_aux=True)(
            x, final_norm, lm_head)
        return ce, lg, grads

    scatter = jax.jit(lambda ct, row: jnp.zeros(params["embed"].shape, jnp.float32)
                      .at[row].add(ct).astype(params["embed"].dtype))
    def add(a, b):
        b = jax.device_get(b)
        return b if a is None else jax.tree.map(
            lambda x, y: (x.astype(np.float32) + y.astype(np.float32)).astype(x.dtype), a, b)

    grads = {"embed": None, "final_norm": None, "lm_head": None, "blocks": [None] * len(blocks)}
    ce, balance, counts, first_row = 0.0, 0.0, 0, {}
    for b in range(n_rows):
        row = rows[b]
        xs, routed = [jax.jit(embed)(params["embed"], row)], []
        for lead, where in blocks:
            x, routing = forward(lead)(xs[-1], leaves(where))
            xs.append(x)
            if routing:
                routed.append(routing)
        counts = counts + jnp.stack([r["rows"] for r in routed])
        balance += float(jnp.mean(jnp.stack([
            _balance(r["rows"], r["share_mean"], seq, top_k) for r in routed]))) / n_rows
        row_ce, lg, (ct, d_norm, d_head) = head_terms(
            xs[-1], params["final_norm"], params["lm_head"], row)
        if b == 0:  # on the host: the backward pass needs the room
            first_row = {"logits": np.asarray(lg),
                         "probs": np.stack([np.asarray(r["probs"]) for r in routed]),
                         "biased": np.stack([np.asarray(r["biased"]) for r in routed])}
        del lg, routed
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        grads["lm_head"] = add(grads["lm_head"], d_head)
        for i in reversed(range(len(blocks))):
            lead, where = blocks[i]
            ct, d_layer = backward(lead)(xs[i], leaves(where), ct)
            grads["blocks"][i] = add(grads["blocks"][i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(ct, row))
    # the blocks' gradients back under the leaves' own names
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm", "lm_head")}
    periods = {}
    for (_, where), d_layer in zip(blocks, grads["blocks"]):
        for leaf, g in d_layer.items():
            if where[0] == "lead_layers":
                by_name[f"['lead_layers']['{where[1]}']['{leaf}']"] = g
            else:
                periods.setdefault(f"['layers']['{leaf}']", []).append(g)
    by_name.update({name: np.stack(gs) for name, gs in periods.items()})
    seen = {**first_row, "ce": ce, "balance": balance, "rows_per_expert": counts}
    return ce + aux_weight * balance, seen, by_name
