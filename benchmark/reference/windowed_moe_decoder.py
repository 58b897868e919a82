"""Plain reference of a decoder that mixes full and window layers of
grouped-query attention with different head counts, a head-wise gate, a
leading dense layer and softmax-routed experts under a routed scale beside a
plain shared expert (Laguna-S-2.1, from its ``config.json``). Straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernel, no sort
of rows, no dispatch: every query scores every key and what it may not see is
masked; EVERY expert held here is applied to every token and masked by the
top-k choice. Blocks over queries (a kv head's group of query heads at a
time) and over rows of the dense MLP only so that 16k positions fit.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. A block: ``x += mixer(n(x));
x += mlp(n(x))``. The leading layers (``lead_pattern``) have a dense SwiGLU,
the periods' layers the expert layer.

Attention, both kinds (H query heads, KV key/value heads of D; ``x`` the
normed input; head h reads kv head ``h // (H / KV)``):

    q_h = x W_q[h];  k_j = x W_k[j];  v_j = x W_v[j];  rope on q and k
    o_h = softmax over the allowed keys of (q_h . k_j D^-1/2) v_j
    y   = concat_h(sigmoid(x W_g)_h o_h) W_o

A full layer allows every key s <= t, a window layer those with
``0 <= t - s < window``. Rope turns the first ``rotary_dim`` features of a
head (their own split halves) by ``f_i = theta^(-2i/d)``, i < d/2, d the
rotated features; under YaRN (``yarn``: ``factor`` F, ``original_length`` L,
``beta_fast``, ``beta_slow``, ``attention_factor`` A) by

    dim(r)     = d ln(L / (2 pi r)) / (2 ln theta)
    lo, hi     = max(floor(dim(beta_fast)), 0), min(ceil(dim(beta_slow)), d - 1)
    m_i        = 1 - clip((i - lo) / (hi - lo), 0, 1)
    inv_freq_i = (f_i / F)(1 - m_i) + f_i m_i

with cos and sin multiplied by A. (The published numbers, d 64, theta
500,000, F 128, L 8,192, 32 and 1: lo 9, hi 18.)

Expert layer: ``p = softmax(h W_r)`` over all X experts; the k largest; gates
``p[e] / sum p[e]`` (``norm_topk``) times ``scale``; ``y = sum_j g_j
E_{e_j}(h)`` over the chosen experts AMONG THOSE HELD (a chip's share; what
absent experts would add is left out, here as in the program) ``+
E_shared(h)``, every expert a SwiGLU. The balance term of a layer, over the N
tokens of all rows and all X experts: ``X sum_x (rows_x / (N k)) mean_n p[n,
x]``, the counts constants.

The loss of rows [B, S]: mean next-token cross entropy + ``aux_weight`` x the
balance term (mean over expert layers). ``loss_and_grads`` makes the same
loss and its gradient a block at a time, so that 1.7 B parameters' float32
copies never exist together. Rope pairs features as split halves (the
published pairing is the same distribution on seeded weights). The weights
are the program's own arrays read by the names of its parameter tree
(``lead_layers/layer<i>/<leaf>``; ``layers/slot<i>/<leaf>`` stacked over the
periods) and upcast to float32. Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, loss_of, position_errors
from .latent_sparse_decoder import _norm, _swiglu, dense_mlp, embed, head, layers_of

__all__ = ["logits", "loss", "loss_and_grads", "block", "layers_of", "embed", "head",
           "gqa_mixer", "expert_layer", "dense_mlp", "yarn_inv_freq", "window_share",
           "loss_of", "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)
# Query rows scored at a time, for one kv head's group of query heads: 9 heads
# x 128 rows x 16,384 keys are 75 MB of float32 scores. Each block is the
# plain softmax over all its keys, recomputed in a backward pass.
QUERY_BLOCK = 128


def yarn_inv_freq(d: int, theta: float, factor: float, original_length: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's d / 2 inverse frequencies (the module's text), float32."""
    f = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    dim = lambda r: d * math.log(original_length / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    lo, hi = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), d - 1)
    m = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return (f / factor) * (1.0 - m) + f * m


def _rope(x, spec: dict):
    """x [S, H, D]: rotate the first ``rotary_dim`` features (0: all),
    positions 0..S-1."""
    s, d = x.shape[0], spec["rotary_dim"] or x.shape[-1]
    yarn = spec.get("yarn")
    if yarn:
        inv = yarn_inv_freq(d, spec["rope_theta"], yarn["factor"], yarn["original_length"],
                            yarn["beta_fast"], yarn["beta_slow"])
    else:
        inv = 1.0 / spec["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    scale = yarn["attention_factor"] if yarn else 1.0
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2, rest = x[..., : d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def window_share(seq: int, window: int) -> float:
    """(query, key) pairs a window layer attends over the causal pairs of a
    row of ``seq``: ``(w T - w (w - 1) / 2) / (T (T + 1) / 2)``, w = min(window, T)."""
    w = min(window, seq)
    return (w * seq - w * (w - 1) / 2) / (seq * (seq + 1) / 2)


def gqa_mixer(h, layer, spec: dict):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``kv_heads``,
    ``head_dim``, ``rope_theta``, ``rotary_dim``, ``yarn`` (a dict or None),
    ``window`` (0: none), ``gate`` ("none" | "headwise")."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, kv_heads, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    group = heads // kv_heads
    q = _rope(mm("se,ehd->shd", h, f32("wq")), spec)
    k = _rope(mm("se,ehd->shd", h, f32("wk")), spec)
    v = mm("se,ehd->shd", h, f32("wv"))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def one_kv_head(xs):
        q_j, k_j, v_j = xs                                    # [S, g, D], [S, D], [S, D]

        @jax.checkpoint
        def rows(q_rows, first):
            scores = mm("qgd,kd->gqk", q_rows, k_j) / math.sqrt(d)
            back = (first + jnp.arange(block))[:, None] - keys[None, :]
            allowed = back >= 0
            if spec["window"]:
                allowed &= back < spec["window"]
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
            return mm("gqk,kd->qgd", probs, v_j)

        out = jax.lax.map(lambda xs: rows(*xs), (q_j.reshape(s // block, block, group, d),
                                                 jnp.arange(0, s, block)))
        return out.reshape(s, group, d)

    attn = jax.lax.map(jax.checkpoint(one_kv_head),
                       (q.reshape(s, kv_heads, group, d).swapaxes(0, 1),
                        k.swapaxes(0, 1), v.swapaxes(0, 1)))   # [KV, S, g, D]
    attn = attn.swapaxes(0, 1).reshape(s, heads, d)
    if spec["gate"] == "headwise":
        attn = attn * jax.nn.sigmoid(mm("se,eh->sh", h, f32("w_attn_gate")))[..., None]
    return mm("shd,hde->se", attn, f32("wo"))


def expert_layer(h, layer, *, top_k: int, norm_topk: bool, first: int = 0,
                 scale: float = 1.0):
    """The expert layer alone on h [S, E] (normed): (y [S, E], routing). The
    router scores all X experts; the ``count`` experts whose weights ``layer``
    holds are experts ``first .. first + count - 1``, each applied to every
    token and weighted by the token's gate for it (0 where it was not chosen);
    the shared expert is added plain. ``routing``: ``probs`` [S, X], ``chosen``
    [S, k], ``rows`` [X] and ``probs_mean`` [X] (what the balance term takes
    of a row)."""
    h = h.astype(jnp.float32)
    probs = jax.nn.softmax(mm("se,ex->sx", h, layer["router"].astype(jnp.float32)), axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * scale
    n_experts = probs.shape[-1]
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
    if "w_shared_gate" in layer:
        y = y + _swiglu(h, layer["w_shared_gate"], layer["w_shared_up"],
                        layer["w_shared_down"])
    return y, {"probs": probs, "chosen": chosen,
               "rows": jax.lax.stop_gradient(jnp.sum(picked, axis=(0, 1))),
               "probs_mean": jnp.mean(probs, axis=0)}


def block(x, layer, kind: str, lead: bool, arch: dict):
    """One decoder block on x [S, E] float32: (x, the expert layer's
    ``routing``, or ``{}`` for a leading layer's dense MLP)."""
    eps = arch["norm_eps"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    x = x + gqa_mixer(h, layer, arch["kinds"][kind])
    h = _norm(x, layer["mlp_norm"].astype(jnp.float32), eps)
    if lead:
        return x + dense_mlp(h, layer), {}
    y, routing = expert_layer(h, layer, top_k=arch["top_k"], norm_topk=arch["norm_topk"],
                              first=arch["held_first"], scale=arch["routed_scale"])
    return x + y, routing


def logits(params, tokens, arch: dict):
    """tokens [S] int32 -> (float32 logits [S, vocab], routing stacked over
    the expert layers). ``arch``: ``kinds`` {mixer name: its spec},
    ``pattern`` and ``lead_pattern`` (mixer names), ``norm_eps``, ``top_k``,
    ``norm_topk``, ``held_first``, ``routed_scale``."""
    x = embed(params["embed"], tokens)
    routings = []
    for layer, kind, lead, _ in layers_of(params, arch):
        # a block is recomputed in a backward pass (its input alone is kept)
        x, routing = jax.checkpoint(
            lambda x, layer, kind=kind, lead=lead: block(x, layer, kind, lead, arch))(x, layer)
        if routing:
            routings.append(routing)
    out = head(x, params["final_norm"], params["lm_head"], arch["norm_eps"])
    return out, jax.tree.map(lambda *a: jnp.stack(a), *routings)


def _balance(rows, probs_mean, n_tokens: int, top_k: int):
    """The balance term, the mean over layers: ``rows`` [L, X] over all the
    tokens, ``probs_mean`` [L, X] their mean probabilities."""
    n_experts = rows.shape[-1]
    return jnp.mean(jnp.sum(n_experts * rows / (n_tokens * top_k) * probs_mean, axis=-1))


def loss(params, rows, arch: dict, *, aux_weight: float, return_seen: bool = False):
    """The training loss of token rows [B, S] (the module's text).
    ``return_seen=True`` returns ``(loss, seen)`` for ``value_and_grad(
    has_aux=True)``: the first row's ``logits`` and router ``probs``, the two
    terms (``ce``, ``balance``) and ``rows_per_expert`` [expert layers, X]."""
    rows = jnp.asarray(rows)

    # a row at a time, recomputed whole in a backward pass
    def one(row):
        lg, routing = logits(params, row, arch)
        return loss_of(lg, row), routing, lg

    ces, routing, lgs = jax.lax.map(jax.checkpoint(one), rows)
    ce = jnp.mean(ces)
    counts = jnp.sum(routing["rows"], axis=0)
    balance = _balance(counts, jnp.mean(routing["probs_mean"], axis=0), rows.size,
                       arch["top_k"])
    total = ce + aux_weight * balance
    if not return_seen:
        return total
    return total, {"logits": lgs[0], "probs": routing["probs"][0], "ce": ce,
                   "balance": balance, "rows_per_expert": counts}


def loss_and_grads(params, rows, arch: dict, *, aux_weight: float) -> tuple:
    """``loss`` and its gradient, a BLOCK at a time and by hand: (loss, seen,
    {leaf path as ``jax.tree_util.keystr`` prints it: the gradient in the
    leaf's own type}). ``seen`` as ``loss``'s, the first row's logits and
    probabilities on the host.

    A first pass over every row keeps the counts (the balance term's
    constants), the cross entropy and the first row's logits; then, a row at
    a time, a forward pass (the first one, where there is one row) keeps each
    block's input, the head gives the
    gradient of its own leaves and of the last hidden state, and each block's
    own ``jax.vjp`` is run under one ``jit`` a kind of block, the balance term
    entering it by the row's mean probabilities against those constants. One
    block's float32 weights, their cotangents and its activations are on the
    device at a time. The same numbers as ``jax.grad`` of ``loss`` (a test
    holds them equal)."""
    rows = jnp.asarray(rows)
    blocks = [(kind, lead, where) for _, kind, lead, where in layers_of(params, arch)]
    n_rows = rows.shape[0]
    n_expert_layers = sum(not lead for _, lead, _ in blocks)

    def leaves(where):
        tree = params[where[0]][where[1]]
        return tree if len(where) == 2 else jax.tree.map(lambda a: a[where[2]], tree)

    @functools.lru_cache(maxsize=None)
    def forward(kind, lead):
        return jax.jit(lambda x, layer: block(x, layer, kind, lead, arch))

    @functools.lru_cache(maxsize=None)
    def backward(kind, lead):
        def pull(x, layer, weights, ct):
            def terms(x, layer):
                y, routing = block(x, layer, kind, lead, arch)
                return y, (jnp.sum(weights * routing["probs_mean"]) if routing
                           else jnp.zeros((), jnp.float32))

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

    def run_forward(row, keep: bool):
        xs, routings = [jax.jit(embed)(params["embed"], row)], []
        for kind, lead, where in blocks:
            x, routing = forward(kind, lead)(xs[-1], leaves(where))
            xs = xs + [x] if keep else [x]
            routings.append(routing)
        return xs, routings

    # the first pass: counts, and what is reported of the first row
    counts, probs_mean, first_row, kept = 0, 0, {}, None
    for b in range(n_rows):
        xs, routings = run_forward(rows[b], keep=n_rows == 1)
        routed = [r for r in routings if r]
        counts = counts + jnp.stack([r["rows"] for r in routed])
        probs_mean = probs_mean + jnp.stack([r["probs_mean"] for r in routed]) / n_rows
        if b == 0:  # on the host: the second pass needs the room
            lg = jax.jit(head, static_argnums=3)(
                xs[-1], params["final_norm"], params["lm_head"], arch["norm_eps"])
            first_row = {"logits": np.asarray(lg),
                         "probs": np.stack([np.asarray(r["probs"]) for r in routed])}
            del lg
        kept = xs if n_rows == 1 else None  # one row: the second pass is this one
        del xs, routings, routed
    balance = _balance(counts, probs_mean, rows.size, arch["top_k"])
    # d(aux_weight x balance) / d(a row's mean probabilities), a layer's [X]
    weights = (aux_weight / n_expert_layers * counts.shape[-1] * counts
               / (rows.size * arch["top_k"]) / n_rows)

    scatter = jax.jit(lambda ct, row: jnp.zeros(params["embed"].shape, jnp.float32)
                      .at[row].add(ct).astype(params["embed"].dtype))
    add = lambda a, b: b if a is None else jax.tree.map(  # noqa: E731
        lambda x, y: (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype), a, b)
    grads = {"embed": None, "final_norm": None, "lm_head": None, "blocks": [None] * len(blocks)}
    ce = 0.0
    for b in range(n_rows):
        row = rows[b]
        xs = kept or run_forward(row, keep=True)[0]
        row_ce, _, (ct, d_norm, d_head) = head_terms(
            xs[-1], params["final_norm"], params["lm_head"], row)
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        grads["lm_head"] = add(grads["lm_head"], d_head)
        layer_weights = iter(reversed(list(weights)))
        for i in reversed(range(len(blocks))):
            kind, lead, where = blocks[i]
            w = jnp.zeros(()) if lead else next(layer_weights)
            ct, d_layer = backward(kind, lead)(xs[i], leaves(where), w, ct)
            grads["blocks"][i] = add(grads["blocks"][i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(ct, row))
    # the blocks' gradients back under the leaves' own names
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm", "lm_head")}
    periods = {}
    for (_, _, where), d_layer in zip(blocks, grads["blocks"]):
        for leaf, g in d_layer.items():
            if where[0] == "lead_layers":
                by_name[f"['lead_layers']['{where[1]}']['{leaf}']"] = g
            else:
                periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: jnp.stack(gs) for name, gs in periods.items()})
    seen = {**first_row, "ce": ce, "balance": float(balance), "rows_per_expert": counts}
    return ce + aux_weight * float(balance), seen, by_name
