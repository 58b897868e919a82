"""Plain reference of a decoder whose router reads the block's input ahead of
attention (SmallThinker-21BA3B-Instruct, from its ``config.json`` and, for
what no key settles, the readings the configuration file lists under
``assumed``): un-roped full layers beside roped layers under a causal window,
grouped queries, softmax-routed ReGLU experts of which a chip holds a share.
Straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision, no
kernel, no sort of rows, no dispatch: every query scores every key and what it
may not see is masked; EVERY expert held here is applied to every token and
masked by the top-k choice. Blocks over queries (a kv head's group of query
heads at a time) only so that 16k positions fit.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. One block, x [S, E] the
residual stream AS IT ENTERS the block (no bias anywhere, no QK-norm):

    r   = x W_r                           float32, X outputs: the router reads
    E   = the k largest of r              x ITSELF, before any norm of the block
    g_e = exp(r_e) / sum_{e' in E} exp(r_e')               softmax over the k chosen
    h   = n(x; w_attn)
    q_j = h W_q[j];  k_i = h W_k[i];  v_i = h W_v[i];  head j reads kv head j // (H / KV)
    a full layer (``rope_theta`` 0): NO rope;           keys s <= t
    a window layer:  rope on all D features, theta;     keys s <= t, t - s < window
    o_j[t] = sum over the allowed s of softmax_s(q_j[t] . k[s] D^-1/2) v[s]
    x'  = x + concat_j(o_j) W_o
    h'  = n(x'; w_mlp)
    x'' = x' + sum over e in E that are HELD of g_e W_down[e] (relu(W_gate[e] h') * W_up[e] h')

Rope turns a head's features as split halves: pair i < D/2 is features (i,
i + D/2), by the angle ``t theta^(-2i/D)`` at position t (the published
pairing is the same distribution on seeded weights). What absent experts would
add is left out, here as in the program. The balance term of a layer, over the
N tokens of all rows and all X experts, p = softmax(r) over all X:
``X sum_x (rows_x / (N k)) mean_n p[n, x]``, the counts constants.

The loss of rows [B, S]: mean next-token cross entropy after a final norm and
an untied head + ``aux_weight`` x the balance term (mean over the layers).
``loss_and_grads`` makes the same loss and its gradient a block at a time, so
that 1.6 B parameters' float32 copies never exist together. The weights are
the program's own arrays read by the names of its parameter tree
(``layers/slot<i>/<leaf>`` stacked over the periods) and upcast to float32.
Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, loss_of, position_errors
from .latent_sparse_decoder import _norm, embed, head, layers_of
from .windowed_moe_decoder import _balance, window_share

__all__ = ["logits", "loss", "loss_and_grads", "block", "layers_of", "embed", "head",
           "gqa_mixer", "gates_of", "expert_layer", "window_share", "loss_of",
           "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)
# Query rows scored at a time, for one kv head's group of query heads: 7 heads
# x 128 rows x 16,384 keys are 59 MB of float32 scores. Each block is the
# plain softmax over all its keys, recomputed in a backward pass.
QUERY_BLOCK = 128
# Positions whose logits ``loss_and_grads`` makes at a time
HEAD_ROWS = 2048


def _rope(x, theta: float):
    """x [S, H, D]: every feature turned, pair i = (i, i + D/2) by
    ``t theta^(-2i/D)``, positions t = 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gqa_mixer(h, layer, spec: dict):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``kv_heads``,
    ``head_dim``, ``rope_theta`` (0: no rope, q and k as projected),
    ``window`` (0: none)."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, kv_heads, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    group = heads // kv_heads
    q = mm("se,ehd->shd", h, f32("wq"))
    k = mm("se,ehd->shd", h, f32("wk"))
    v = mm("se,ehd->shd", h, f32("wv"))
    if spec["rope_theta"]:
        q, k = _rope(q, spec["rope_theta"]), _rope(k, spec["rope_theta"])
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
    return mm("shd,hde->se", attn.swapaxes(0, 1).reshape(s, heads, d), f32("wo"))


def gates_of(r, top_k: int):
    """Router logits r [S, X] -> (gates [S, k], chosen [S, k]): the k largest
    LOGITS, then softmax over those k (the published order)."""
    top, chosen = jax.lax.top_k(r, top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def _reglu(h, w_gate, w_up, w_down):
    ff = (jax.nn.relu(mm("se,em->sm", h, w_gate.astype(jnp.float32)))
          * mm("se,em->sm", h, w_up.astype(jnp.float32)))
    return mm("sm,me->se", ff, w_down.astype(jnp.float32))


def expert_layer(h, x_in, layer, *, top_k: int, first: int = 0):
    """The expert layer alone: the experts read h [S, E] (normed), the router
    reads x_in [S, E] (the block's input): (y [S, E], routing). The router
    scores all X experts; the ``count`` experts whose weights ``layer`` holds
    are experts ``first .. first + count - 1``, each applied to every token
    and weighted by the token's gate for it (0 where it was not chosen).
    ``routing``: ``probs`` [S, X] (softmax over all X, for the balance term
    and for telling ties), ``chosen`` [S, k], ``rows`` [X] and ``probs_mean``
    [X] (what the balance term takes of a row)."""
    h = h.astype(jnp.float32)
    r = mm("se,ex->sx", x_in.astype(jnp.float32), layer["router"].astype(jnp.float32))
    gates, chosen = gates_of(r, top_k)
    probs = jax.nn.softmax(r, axis=-1)
    picked = jax.nn.one_hot(chosen, r.shape[-1], dtype=jnp.float32)          # [S, k, X]
    weights = jnp.einsum("sk,skx->sx", gates, picked)
    count = layer["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    # an expert's weighted part is recomputed in a backward pass
    @jax.checkpoint
    def part(w_gate, w_up, w_down, weight):
        return weight[:, None] * _reglu(h, w_gate, w_up, w_down)

    y, _ = jax.lax.scan(lambda y, xs: (y + part(*xs), None), jnp.zeros_like(h),
                        (layer["w_gate"], layer["w_up"], layer["w_down"], held.T))
    return y, {"probs": probs, "chosen": chosen,
               "rows": jax.lax.stop_gradient(jnp.sum(picked, axis=(0, 1))),
               "probs_mean": jnp.mean(probs, axis=0)}


def block(x, layer, kind: str, arch: dict):
    """One decoder block on x [S, E] float32: (x, the expert layer's
    ``routing``). The router reads ``x`` as given, before either norm."""
    eps = arch["norm_eps"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    x_mid = x + gqa_mixer(h, layer, arch["kinds"][kind])
    h = _norm(x_mid, layer["mlp_norm"].astype(jnp.float32), eps)
    y, routing = expert_layer(h, x, layer, top_k=arch["top_k"], first=arch["held_first"])
    return x_mid + y, routing


def logits(params, tokens, arch: dict):
    """tokens [S] int32 -> (float32 logits [S, vocab], routing stacked over
    the layers). ``arch``: ``kinds`` {mixer name: its spec}, ``pattern``
    (mixer names of a period; ``lead_pattern`` is ()), ``norm_eps``, ``top_k``,
    ``held_first``."""
    x = embed(params["embed"], tokens)
    routings = []
    for layer, kind, _, _ in layers_of(params, arch):
        # a block is recomputed in a backward pass (its input alone is kept)
        x, routing = jax.checkpoint(
            lambda x, layer, kind=kind: block(x, layer, kind, arch))(x, layer)
        routings.append(routing)
    out = head(x, params["final_norm"], params["lm_head"], arch["norm_eps"])
    return out, jax.tree.map(lambda *a: jnp.stack(a), *routings)


def loss(params, rows, arch: dict, *, aux_weight: float, return_seen: bool = False):
    """The training loss of token rows [B, S] (the module's text).
    ``return_seen=True`` returns ``(loss, seen)`` for ``value_and_grad(
    has_aux=True)``: the first row's ``logits`` and router ``probs``, the two
    terms (``ce``, ``balance``) and ``rows_per_expert`` [layers, X]."""
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
    constants), and of the first row the logits; then, a row at a time, a
    forward pass (the first one, where there is one row) keeps each block's
    input, the head gives the gradient of its own leaves and of the last
    hidden state, and each block's own ``jax.vjp`` is run under one ``jit`` a
    kind of block, the balance term entering it by the row's mean
    probabilities against those constants. One block's float32 weights, their
    cotangents and its activations are on the device at a time. The same
    numbers as ``jax.grad`` of ``loss`` (a test holds them equal)."""
    rows = jnp.asarray(rows)
    blocks = [(kind, where) for _, kind, _, where in layers_of(params, arch)]
    n_rows = rows.shape[0]

    def leaves(where):
        return jax.tree.map(lambda a: a[where[2]], params[where[0]][where[1]])

    @functools.lru_cache(maxsize=None)
    def forward(kind):
        return jax.jit(lambda x, layer: block(x, layer, kind, arch))

    @functools.lru_cache(maxsize=None)
    def backward(kind):
        def pull(x, layer, weights, ct):
            def terms(x, layer):
                y, routing = block(x, layer, kind, arch)
                return y, jnp.sum(weights * routing["probs_mean"])

            return jax.vjp(terms, x, layer)[1]((ct, jnp.ones((), jnp.float32)))

        return jax.jit(pull)

    @jax.jit
    def head_terms(x, final_norm, lm_head, row):
        """The row's share of the cross entropy and its gradient with respect
        to (x, final_norm, lm_head), HEAD_ROWS positions at a time: [2048, V]
        float32 logits and their cotangent, never [S, V] (2.5 GB each at
        16,384 x 37,984). Sums in float32, rounded once to a leaf's type."""
        s, e = x.shape
        rows_at_once = HEAD_ROWS if s % HEAD_ROWS == 0 else s
        weight = (jnp.arange(s) < s - 1) / ((s - 1) * n_rows)   # the last position has no target
        norm32, head32 = final_norm.astype(jnp.float32), lm_head.astype(jnp.float32)

        def nll(x, norm32, head32, targets, weight):
            lg = head(x, norm32, head32, arch["norm_eps"])
            ll = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), targets[:, None], axis=-1)
            return -jnp.sum(ll[:, 0] * weight)

        def chunk(carry, xs):
            value, grads = jax.value_and_grad(nll, argnums=(0, 1, 2))(
                xs[0], norm32, head32, *xs[1:])
            return (carry[0] + value, carry[1] + grads[1], carry[2] + grads[2]), grads[0]

        parts = lambda a: a.reshape((s // rows_at_once, rows_at_once) + a.shape[1:])  # noqa: E731
        (ce, d_norm, d_head), d_x = jax.lax.scan(
            chunk, (jnp.zeros(()), jnp.zeros_like(norm32), jnp.zeros_like(head32)),
            (parts(x), parts(jnp.roll(row, -1)), parts(weight)))
        return ce, (d_x.reshape(s, e), d_norm.astype(final_norm.dtype),
                    d_head.astype(lm_head.dtype))

    def run_forward(row, keep: bool):
        xs, routings = [jax.jit(embed)(params["embed"], row)], []
        for kind, where in blocks:
            x, routing = forward(kind)(xs[-1], leaves(where))
            xs = xs + [x] if keep else [x]
            routings.append(routing)
        return xs, routings

    # the first pass: counts, and what is reported of the first row
    counts, probs_mean, first_row, kept = 0, 0, {}, None
    for b in range(n_rows):
        xs, routings = run_forward(rows[b], keep=n_rows == 1)
        counts = counts + jnp.stack([r["rows"] for r in routings])
        probs_mean = probs_mean + jnp.stack([r["probs_mean"] for r in routings]) / n_rows
        if b == 0:  # on the host: the second pass needs the room
            lg = jax.jit(head, static_argnums=3)(
                xs[-1], params["final_norm"], params["lm_head"], arch["norm_eps"])
            first_row = {"logits": np.asarray(lg),
                         "probs": np.stack([np.asarray(r["probs"]) for r in routings])}
            del lg
        kept = xs if n_rows == 1 else None  # one row: the second pass is this one
        del xs, routings
    balance = _balance(counts, probs_mean, rows.size, arch["top_k"])
    # d(aux_weight x balance) / d(a row's mean probabilities), a layer's [X]
    weights = (aux_weight / len(blocks) * counts.shape[-1] * counts
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
        row_ce, (ct, d_norm, d_head) = head_terms(
            xs[-1], params["final_norm"], params["lm_head"], row)
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        grads["lm_head"] = add(grads["lm_head"], d_head)
        for i in reversed(range(len(blocks))):
            kind, where = blocks[i]
            ct, d_layer = backward(kind)(xs[i], leaves(where), weights[i], ct)
            grads["blocks"][i] = add(grads["blocks"][i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(ct, row))
    # the blocks' gradients back under the leaves' own names, stacked over the periods
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm", "lm_head")}
    periods = {}
    for (_, where), d_layer in zip(blocks, grads["blocks"]):
        for leaf, g in d_layer.items():
            periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: jnp.stack(gs) for name, gs in periods.items()})
    seen = {**first_row, "ce": ce, "balance": float(balance), "rows_per_expert": counts}
    return ce + aux_weight * float(balance), seen, by_name
