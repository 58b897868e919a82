"""Plain reference of a decoder of double-gated short convolutions beside roped
grouped-query attention with per-head q/k norms, leading dense layers, sigmoid-
routed experts under a selection bias with no shared expert and a tied
vocabulary (LFM2-8B-A1B, ``model_type`` ``lfm2_moe``, from its ``config.json``
and, for what no key settles, the readings the configuration file lists under
``assumed``). Straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernel, no sort of rows, no dispatch: the conv is a scan over
POSITIONS that carries the last K - 1 of them, every query scores every key and
masks what it may not attend, EVERY expert held here is applied to every token
and masked by the top-k choice. Blocks of queries and of MLP rows only so that
8k positions fit.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. The stack, x [S, E] the stream,
E the ONE table [V, E]:

    x_0    = E[ids]
    x     += mixer(n(x; w_op));   x += ffn(n(x; w_ffn))
    logits = n(x_L; w_final) E^T                      (the class names the final
                                                       norm ``embedding_norm``)

A ``conv`` layer, h the normed input, K taps:

    [B | C | X] = h W_in;   u = B * X
    v_t = sum_i k_i u_{t-(K-1)+i}                     zeros before the row, no bias
    out = (C * v) W_out                               no activation, no norm

A ``full_attention`` layer, H query heads over KV kv heads of D:

    q = n_D(h W_q; w_q);  k = n_D(h W_k; w_k);  v = h W_v      the norm a head's D features
    q, k = rope(q), rope(k)                            all D features, split halves
    o_j[t] = softmax over the keys s <= t of (D^-1/2 q_j[t] . k[s]) v[s];   out = o W_o

The leading layers' ffn is ``(silu(h W_1) * h W_3) W_2``. An expert layer:
``s = sigmoid(h W_g)`` over all X experts; the k experts of largest ``s + b``;
gates ``scale s[e] / (sum s[e] + 1e-6)`` (the published guard; the program
divides by ``max(sum, 1e-9)``: 4 sigmoid scores sum to ~2, so the two differ
by 5e-7 of a gate); ``y = sum_j g_j E_{e_j}(h)`` over the chosen experts AMONG
THOSE HELD (a chip's share: ``held_first`` and as many as the leaves hold; what
absent experts would add is left out, here as in the program), every expert a
SwiGLU; no shared expert. The balance term, a sequence at a time over all X
experts: ``sum_x (X rows_x / (k S)) mean_t (s / sum_x s)[t, x]``, the counts
constants. The loss of rows [B, S]: mean next-token cross entropy +
``aux_weight`` x the balance term (mean over rows and expert layers).

Departures from the published class, each without a number of its own: ``W_in``
is held as [E, 3, E] (its column thirds B, C, X), the same numbers; the conv
runs position by position and not as a grouped ``Conv1d`` over a padded row.

``faults`` (a set of names, the runner's controls that change the REFERENCE:
``no_c_gate``, ``silu_after_conv``, ``no_rope``, ``bias_on_gates``) each put one
misreading in the mathematics' place, for showing that a comparison refuses
it. The weights are the program's own arrays read by the names of its parameter
tree (``lead_layers/layer<i>/<leaf>``; ``layers/slot<i>/<leaf>`` stacked over
the periods) and upcast to float32. Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, _rope, loss_of, position_errors
from .latent_decoder import _balance
from .latent_sparse_decoder import _norm, embed, layers_of

__all__ = ["logits", "loss", "loss_and_grads", "block", "conv_mixer", "attention_mixer",
           "expert_layer", "dense_mlp", "layers_of", "loss_of", "position_errors"]

# the precision of every product; the runner's control lowers it
PRECISION = [HIGHEST]
# Query rows scored at a time, for one kv head's group of query heads: 4 heads
# x 256 rows x 8,192 keys are 34 MB of float32 scores
QUERY_BLOCK = 256
# Rows of a dense MLP or of an expert at a time
MLP_ROWS = 2048
# Positions whose logits ``loss_and_grads`` makes at a time
HEAD_ROWS = 2048
# Positions of the conv's scan written out in one turn of its loop
SCAN_UNROLL = 32


def mm(*args):
    return jnp.einsum(*args, precision=PRECISION[0])


def conv_mixer(h, layer, faults=frozenset()):
    """h [S, E] (normed) -> (y [S, E], past_share): the conv position by
    position, carrying the K - 1 positions before. ``past_share``:
    ``|v - k_{K-1} u|^2 / |v|^2``."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    b, c, x = mm("se,egc->gsc", h, f32("w_in"))
    u, taps = b * x, f32("conv")

    def position(before, u_t):
        past = jnp.sum(taps[:-1] * before, axis=0)
        return jnp.concatenate([before[1:], u_t[None]]), (past + taps[-1] * u_t, past)

    # unrolled: the loop's own cost a position, not the mathematics, is what
    # 8,192 turns of it take on a chip
    _, (v, past) = jax.lax.scan(position, jnp.zeros((taps.shape[0] - 1, u.shape[1])), u,
                                unroll=SCAN_UNROLL)
    share = jax.lax.stop_gradient(jnp.sum(past * past) / jnp.sum(v * v))
    if "silu_after_conv" in faults:
        v = jax.nn.silu(v)
    if "no_c_gate" not in faults:
        v = c * v
    return mm("sc,ce->se", v, f32("w_out")), share


def attention_mixer(h, layer, spec: dict, eps: float, faults=frozenset()):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``kv_heads``,
    ``head_dim``, ``rope_theta``."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, kv_heads, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    group = heads // kv_heads
    q = _norm(mm("se,ehd->shd", h, f32("wq")), f32("q_norm"), eps)
    k = _norm(mm("se,ehd->shd", h, f32("wk")), f32("k_norm"), eps)
    v = mm("se,ehd->shd", h, f32("wv"))
    if "no_rope" not in faults:
        q, k = _rope(q, spec["rope_theta"]), _rope(k, spec["rope_theta"])
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def one_kv_head(xs):
        q_j, k_j, v_j = xs                                  # [S, g, D], [S, D], [S, D]

        @jax.checkpoint
        def rows(q_rows, first):
            scores = mm("qgd,kd->gqk", q_rows, k_j) / math.sqrt(d)
            allowed = (first + jnp.arange(block))[:, None] >= keys[None, :]
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
            return mm("gqk,kd->qgd", probs, v_j)

        out = jax.lax.map(lambda xs: rows(*xs), (q_j.reshape(s // block, block, group, d),
                                                 jnp.arange(0, s, block)))
        return out.reshape(s, group, d)

    attn = jax.lax.map(jax.checkpoint(one_kv_head),
                       (q.reshape(s, kv_heads, group, d).swapaxes(0, 1),
                        k.swapaxes(0, 1), v.swapaxes(0, 1)))            # [KV, S, g, D]
    return mm("shd,hde->se", attn.swapaxes(0, 1).reshape(s, heads, d), f32("wo"))


def _swiglu(h, w_gate, w_up, w_down):
    ff = (jax.nn.silu(mm("se,em->sm", h, w_gate.astype(jnp.float32)))
          * mm("se,em->sm", h, w_up.astype(jnp.float32)))
    return mm("sm,me->se", ff, w_down.astype(jnp.float32))


def dense_mlp(h, layer):
    h = h.astype(jnp.float32)
    rows = MLP_ROWS if h.shape[0] % MLP_ROWS == 0 else h.shape[0]
    part = jax.checkpoint(lambda x: _swiglu(x, layer["w_gate"], layer["w_up"], layer["w_down"]))
    return jax.lax.map(part, h.reshape(-1, rows, h.shape[1])).reshape(h.shape)


def expert_layer(h, layer, *, top_k: int, norm_topk: bool, first: int = 0,
                 scale: float = 1.0, faults=frozenset()):
    """The expert layer alone on h [S, E] (normed): (y [S, E], routing). The
    router scores all X experts; the ``count`` experts whose weights ``layer``
    holds are experts ``first .. first + count - 1``, each applied to every
    token and weighted by the token's gate for it (0 where it was not chosen).
    ``routing``: ``probs`` (the sigmoid scores), ``biased`` (what the choice
    ranks), ``chosen``, ``rows`` [X] (constants) and ``share_mean`` [X] (what
    the balance term takes of this sequence)."""
    h = h.astype(jnp.float32)
    scores = jax.nn.sigmoid(mm("se,ex->sx", h, layer["router"].astype(jnp.float32)))
    biased = scores + jax.lax.stop_gradient(layer["router_bias"].astype(jnp.float32))
    chosen = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)[1]
    gates = jnp.take_along_axis(biased if "bias_on_gates" in faults else scores, chosen, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
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
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return y, {"probs": scores, "biased": biased, "chosen": chosen,
               "rows": jax.lax.stop_gradient(jnp.sum(picked, axis=(0, 1))),
               "share_mean": jnp.mean(share, axis=0)}


def block(x, layer, kind: str, lead: bool, arch: dict):
    """One decoder block on x [S, E] float32: (x, the expert layer's ``routing``
    or ``{}`` for a leading layer's dense MLP, the conv's ``past_share`` or
    None)."""
    eps, faults = arch["norm_eps"], arch.get("faults", frozenset())
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    past_share = None
    if kind == "sconv":
        mixed, past_share = conv_mixer(h, layer, faults)
    else:
        mixed = attention_mixer(h, layer, arch["attn"], eps, faults)
    x = x + mixed
    h = _norm(x, layer["mlp_norm"].astype(jnp.float32), eps)
    if lead:
        return x + dense_mlp(h, layer), {}, past_share
    y, routing = expert_layer(h, layer, top_k=arch["top_k"], norm_topk=arch["norm_topk"],
                              first=arch["held_first"], scale=arch["routed_scale"],
                              faults=faults)
    return x + y, routing, past_share


def head(x, final_norm, table, eps: float):
    """The tied head: the final norm's output against the embedding's own rows
    [V, E]."""
    return mm("se,ve->sv", _norm(x, final_norm.astype(jnp.float32), eps),
              table.astype(jnp.float32))


def _leaves(params, where):
    tree = params[where[0]][where[1]]
    return tree if len(where) == 2 else jax.tree.map(lambda a: a[where[2]], tree)


def logits(params, tokens, arch: dict):
    """tokens [S] int32 -> (float32 logits [S, vocab], seen). ``arch``:
    ``pattern`` and ``lead_pattern`` (mixer names: ``sconv`` | ``attn``),
    ``attn`` (the attention layers' spec), ``norm_eps``, ``top_k``,
    ``norm_topk``, ``held_first``, ``routed_scale``, and ``faults`` where a
    control plants some. ``seen``: the expert layers' ``routing`` stacked, and
    ``past_share``, the mean over the conv layers."""
    x = embed(params["embed"], tokens)
    routings, shares = [], []
    for _, kind, lead, where in layers_of(params, arch):
        # a block is recomputed in a backward pass (its input alone is kept)
        x, routing, past_share = jax.checkpoint(
            lambda x, layer, kind=kind, lead=lead: block(x, layer, kind, lead, arch))(
                x, _leaves(params, where))
        if routing:
            routings.append(routing)
        if past_share is not None:
            shares.append(past_share)
    out = head(x, params["final_norm"], params["embed"], arch["norm_eps"])
    return out, {"routing": jax.tree.map(lambda *a: jnp.stack(a), *routings),
                 "past_share": jnp.mean(jnp.stack(shares))}


def loss(params, rows, arch: dict, *, aux_weight: float, return_seen: bool = False):
    """The training loss of token rows [B, S] (the module's text).
    ``return_seen=True`` returns ``(loss, seen)`` for ``value_and_grad(
    has_aux=True)``: the first row's ``logits``, router ``probs`` and
    ``biased``, the two terms (``ce``, ``balance``), ``rows_per_expert``
    [expert layers, X] over all rows and ``past_share``."""
    rows = jnp.asarray(rows)

    # a row at a time, recomputed whole in a backward pass
    def one(row):
        lg, seen = logits(params, row, arch)
        return loss_of(lg, row), seen, lg

    ces, seen, lgs = jax.lax.map(jax.checkpoint(one), rows)
    routing = seen["routing"]
    ce = jnp.mean(ces)
    balance = jnp.mean(_balance(routing["rows"], routing["share_mean"], rows.shape[1],
                                arch["top_k"]))
    total = ce + aux_weight * balance
    if not return_seen:
        return total
    return total, {"logits": lgs[0], "probs": routing["probs"][0],
                   "biased": routing["biased"][0], "ce": ce, "balance": balance,
                   "rows_per_expert": jnp.sum(routing["rows"], axis=0),
                   "past_share": jnp.mean(seen["past_share"])}


def loss_and_grads(params, rows, arch: dict, *, aux_weight: float) -> tuple:
    """``loss`` and its gradient, a BLOCK at a time and by hand: (loss, seen,
    {leaf path as ``jax.tree_util.keystr`` prints it: the gradient, float32,
    on the host}). ``seen`` as ``loss``'s, the first row's logits, scores and
    biased scores on the host.

    A row at a time: a forward pass keeps each block's input, the head gives
    the gradient of its own leaves and of the last hidden state, and each
    block's own ``jax.vjp`` is run under one ``jit`` a kind of block, the
    balance term entering it by the row's ``share_mean`` against that row's own
    counts (the term is taken a sequence at a time). One block's float32
    weights, their cotangents and its activations are on the device at a time;
    the rows' gradients are summed in float32 on the host. The table's gradient
    is the sum of its two uses: the head's product and the rows the ids name.
    The same numbers as ``jax.grad`` of ``loss`` (a test holds them equal)."""
    rows = jnp.asarray(rows)
    blocks = [(kind, lead, where) for _, kind, lead, where in layers_of(params, arch)]
    n_rows, seq = rows.shape
    n_expert_layers = sum(not lead for _, lead, _ in blocks)
    top_k, eps = arch["top_k"], arch["norm_eps"]

    @functools.lru_cache(maxsize=None)
    def forward(kind, lead):
        return jax.jit(lambda x, layer: block(x, layer, kind, lead, arch))

    @functools.lru_cache(maxsize=None)
    def backward(kind, lead):
        def pull(x, layer, ct):
            def terms(x, layer):
                y, routing, _ = block(x, layer, kind, lead, arch)
                if not routing:
                    return y, jnp.zeros((), jnp.float32)
                # this row's and this layer's part of aux_weight x balance
                return y, (aux_weight / (n_expert_layers * n_rows)
                           * _balance(routing["rows"], routing["share_mean"], seq, top_k))

            return jax.vjp(terms, x, layer)[1]((ct, jnp.ones((), jnp.float32)))

        return jax.jit(pull)

    @jax.jit
    def head_terms(x, final_norm, table, row):
        """The row's share of the cross entropy and its gradient with respect
        to (x, final_norm, table), HEAD_ROWS positions at a time. Sums in
        float32."""
        s, e = x.shape
        rows_at_once = HEAD_ROWS if s % HEAD_ROWS == 0 else s
        weight = (jnp.arange(s) < s - 1) / ((s - 1) * n_rows)   # the last position has no target
        norm32, table32 = final_norm.astype(jnp.float32), table.astype(jnp.float32)

        def nll(x, norm32, table32, targets, weight):
            lg = head(x, norm32, table32, eps)
            ll = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), targets[:, None], axis=-1)
            return -jnp.sum(ll[:, 0] * weight)

        def chunk(carry, xs):
            value, grads = jax.value_and_grad(nll, argnums=(0, 1, 2))(
                xs[0], norm32, table32, *xs[1:])
            return (carry[0] + value, carry[1] + grads[1], carry[2] + grads[2]), grads[0]

        parts = lambda a: a.reshape((s // rows_at_once, rows_at_once) + a.shape[1:])  # noqa: E731
        (ce, d_norm, d_table), d_x = jax.lax.scan(
            chunk, (jnp.zeros(()), jnp.zeros_like(norm32), jnp.zeros_like(table32)),
            (parts(x), parts(jnp.roll(row, -1)), parts(weight)))
        return ce, (d_x.reshape(s, e), d_norm, d_table)

    # the table's other use: the rows the ids name, float32 into the head's sum
    scatter = jax.jit(lambda d_table, ct, row: d_table.at[row].add(ct))

    def add(a, b):
        b = jax.tree.map(lambda g: np.asarray(g, np.float32), jax.device_get(b))
        return b if a is None else jax.tree.map(np.add, a, b)

    grads = {"embed": None, "final_norm": None, "blocks": [None] * len(blocks)}
    ce, balance, counts, shares, first_row = 0.0, 0.0, 0, [], {}
    for b in range(n_rows):
        row = rows[b]
        xs, routed = [jax.jit(embed)(params["embed"], row)], []
        for kind, lead, where in blocks:
            x, routing, past_share = forward(kind, lead)(xs[-1], _leaves(params, where))
            xs.append(x)
            if routing:
                routed.append(routing)
            if past_share is not None:
                shares.append(float(past_share))
        counts = counts + jnp.stack([r["rows"] for r in routed])
        balance += float(jnp.mean(jnp.stack([
            _balance(r["rows"], r["share_mean"], seq, top_k) for r in routed]))) / n_rows
        if b == 0:  # on the host: the backward pass needs the room
            lg = jax.jit(head, static_argnums=3)(
                xs[-1], params["final_norm"], params["embed"], eps)
            first_row = {"logits": np.asarray(lg),
                         "probs": np.stack([np.asarray(r["probs"]) for r in routed]),
                         "biased": np.stack([np.asarray(r["biased"]) for r in routed])}
            del lg
        del routed
        row_ce, (ct, d_norm, d_table) = head_terms(
            xs[-1], params["final_norm"], params["embed"], row)
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        for i in reversed(range(len(blocks))):
            kind, lead, where = blocks[i]
            ct, d_layer = backward(kind, lead)(xs[i], _leaves(params, where), ct)
            grads["blocks"][i] = add(grads["blocks"][i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(d_table, ct, row))
        del d_table
    # the blocks' gradients back under the leaves' own names
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm")}
    periods = {}
    for (_, _, where), d_layer in zip(blocks, grads["blocks"]):
        for leaf, g in d_layer.items():
            if where[0] == "lead_layers":
                by_name[f"['lead_layers']['{where[1]}']['{leaf}']"] = g
            else:
                periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: np.stack(gs) for name, gs in periods.items()})
    seen = {**first_row, "ce": ce, "balance": balance, "rows_per_expert": counts,
            "past_share": float(np.mean(shares))}
    return ce + aux_weight * balance, seen, by_name
