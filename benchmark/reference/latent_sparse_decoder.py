"""Plain reference of a latent-attention decoder with a learned selection of
keys (dots3-note-prev, from its ``config.json``; the mechanisms as DeepSeek-V2
published latent attention, DeepSeek-V3 the sigmoid router with its bias and
DeepSeek-V3.2 the indexer). Straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, no kernel, no sort of rows, no dispatch: every query
scores every key and what it may not see is masked; EVERY expert held here
is applied to every token and masked by the top-k choice. Blocks over
queries, groups of heads and rows of the dense MLP only so that it fits.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. A block: ``x += mixer(n(x));
x += mlp(n(x))``. The leading layers (``lead_pattern``) have a dense SwiGLU,
the periods' layers the expert layer.

Latent attention, both kinds (ranks r_q, r_kv; H heads; d_n + d_r query and
key features, d_v value features; ``x`` the normed input):

    c_q = s_q n(x W_dq);  q_h = c_q W_uq[h], rope on its last d_r
    [c_kv | k_r] = x W_dkv;  c_kv = s_kv n(c_kv);  k_r = rope(k_r)
    [k_h^n | v_h] = c_kv W_ukv[h];  k_h = [k_h^n | k_r]
    o_h = softmax over the allowed keys of (q_h . k_h (d_n + d_r)^-1/2) v_h
    y = concat_h(sigmoid(x W_g)_h o_h) W_o

``s_q = (E / r_q)^1/2``, ``s_kv = (E / r_kv)^1/2`` (``rescale``). A window
layer allows keys s in [t - window + 1, t]. A full layer's indexer allows the
``top_k`` keys of largest ``I[t, s] = sum_j w^I[t, j] relu(q^I_j[t] . k^I[s])``,
s <= t (all while t < top_k; a tie at the last place keeps every tied key):
``q^I_j = c_q W_iq[j]`` and ``k^I = layernorm(x W_ik)``, rope on the first d_r
features of both, ``w^I = x W_iw J^-1/2 Di^-1/2``. ``key_sets`` puts given
sets in the indexer's place (the program's own, for a comparison that the
k-th and (k+1)-th key swapping on rounding does not decide).

The indexer's loss, a full layer's: ``mean_t KL(p[t, S_t] || softmax(I[t,
S_t]))``, ``p`` the attention's probabilities summed over heads and normalised
over S_t, a constant; the indexer's inputs ``x`` and ``c_q`` are constants too,
so the term reaches the indexer's leaves alone, and the model's loss none of
them.

Expert layer: ``sc = sigmoid(h W_r)`` over all X experts; the k experts of
largest ``sc + b``; gates ``sc[e] / sum sc[e]``; ``y = sum_j g_j E_{e_j}(h)``
over the chosen experts AMONG THOSE HELD (a chip's share; what absent experts
would add is left out, here as in the program) ``+ E_shared(h)``, every expert
a SwiGLU. The balance term, a sequence at a time over all X experts:
``sum_x (X rows_x / (k S)) mean_t (sc / sum_x sc)[t, x]``.

The loss of rows [B, S]: mean next-token cross entropy + ``aux_weight`` x the
balance term (mean over rows and expert layers) + the
indexer's loss (mean over rows and full layers). ``bias_after`` is the
selection bias's own step. Rope pairs features as split halves (the published
interleaving is the same distribution on seeded weights). The weights are the
program's own arrays read by the names of its parameter tree
(``lead_layers/layer<i>/<leaf>``; ``layers/slot<i>/<leaf>`` stacked over the
periods) and upcast to float32. Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dense_decoder import HIGHEST, loss_of, position_errors

__all__ = ["logits", "loss", "block", "layers_of", "embed", "head", "mla_mixer",
           "expert_layer", "dense_mlp", "select", "bias_after", "loss_of", "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)
INDEX_NORM_EPS = 1e-6
# Query rows scored at a time: 128 heads x 128 rows x 8,192 keys are 537 MB of
# float32 scores. Each block is the plain softmax over all its keys,
# recomputed in a backward pass.
QUERY_BLOCK = 128
# Heads whose q, k and v exist at a time: all 128 of a full layer are 2.7 GB of
# float32 a row of 8,192, and as much again for their cotangents.
HEAD_GROUP = 16
# Rows of the leading dense MLP at a time (13,824 wide: 0.45 GB a tensor whole).
MLP_ROWS = 1024


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta, first: int, count: int):
    """x [S, H, D]: rotate features first .. first + count - 1 (their own
    split halves), positions 0..S-1."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, count, 2, dtype=jnp.float32) / count)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., first:first + count // 2], x[..., first + count // 2:first + count]
    return jnp.concatenate([x[..., :first], x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., first + count:]], axis=-1)


def select(scores, top_k: int):
    """Key sets [S, S] bool from index scores [S, S]: s <= t and I[t, s] at
    least the ``top_k``-th largest of I[t, :t + 1]."""
    s = scores.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    if top_k >= s:
        return causal
    ranked = jnp.sort(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return causal & (scores >= ranked[:, s - top_k][:, None])


def mla_mixer(h, layer, spec: dict, eps: float, key_set=None):
    """h [S, E] (normed) -> (y [S, E], seen). ``spec``: ``heads``, ``q_rank``,
    ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim``, ``rope_theta``,
    ``window``, ``index_heads``, ``index_dim``, ``index_top_k``, ``rescale``,
    ``gate``. ``seen``: an indexed layer's ``selection`` [S, S] bool (its own,
    whatever ``key_set`` says), ``allowed`` (what attention used) and
    ``index_loss``; ``{}`` for a window layer.

    So that it fits: the heads are taken HEAD_GROUP at a time (a group's q, k
    and v are made from the latents where they are used and made again in a
    backward pass) and the queries QUERY_BLOCK at a time. Every query still
    scores every key, in float32."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s, e = h.shape
    heads, d_n, d_r, d_v = spec["heads"], spec["nope_dim"], spec["rope_dim"], spec["v_dim"]
    theta, r_kv = spec["rope_theta"], spec["kv_rank"]
    s_q = (e / spec["q_rank"]) ** 0.5 if spec["rescale"] else 1.0
    s_kv = (e / r_kv) ** 0.5 if spec["rescale"] else 1.0
    c_q = s_q * _norm(mm("se,er->sr", h, f32("w_dq")), f32("q_a_norm"), eps)
    down = mm("se,er->sr", h, f32("w_dkv"))
    c_kv = s_kv * _norm(down[:, :r_kv], f32("kv_a_norm"), eps)
    k_r = _rope(down[:, None, r_kv:], theta, 0, d_r)[:, 0]                # [S, d_r]
    scale = (d_n + d_r) ** -0.5
    positions = jnp.arange(s)
    indexed = bool(spec.get("index_heads"))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    n_blocks = s // block
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    n_groups = heads // group
    by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[:axis] + (n_groups, group) + w.shape[axis + 1:]), axis, 0)
    w_uq, w_ukv = by_group(f32("w_uq"), 1), by_group(f32("w_ukv"), 1)

    own = scores = None
    if indexed:
        # the indexer's inputs are constants: its loss moves its own leaves
        hc, cc = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
        q_i = _rope(mm("sr,rjd->sjd", cc, f32("w_iq")), theta, 0, d_r)
        k_i = mm("se,ed->sd", hc, f32("w_ik"))
        k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
        k_i = (k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True)
                                   + INDEX_NORM_EPS) * f32("ik_norm") + f32("ik_bias"))
        k_i = _rope(k_i[:, None], theta, 0, d_r)[:, 0]
        w_i = (mm("se,ej->sj", hc, f32("w_iw"))
               * spec["index_heads"] ** -0.5 * spec["index_dim"] ** -0.5)

        @jax.checkpoint
        def index_rows(q_rows, w_rows):
            return jnp.einsum("qjk,qj->qk", jax.nn.relu(mm("qjd,kd->qjk", q_rows, k_i)),
                              w_rows, precision=HIGHEST)

        scores = jax.lax.map(lambda xs: index_rows(*xs), (
            q_i.reshape(n_blocks, block, *q_i.shape[1:]),
            w_i.reshape(n_blocks, block, -1))).reshape(s, s)
        own = select(jax.lax.stop_gradient(scores), spec["index_top_k"])
        allowed = own if key_set is None else key_set.astype(bool)
    else:
        gap = positions[:, None] - positions[None, :]
        allowed = (gap >= 0) & ((gap < spec["window"]) if spec.get("window") else True)
    allowed_blocks = allowed.reshape(n_blocks, block, s)

    def group_qkv(w_q, w_kv):
        q = _rope(mm("sr,rhd->shd", c_q, w_q), theta, d_n, d_r)
        kv = mm("sr,rhd->shd", c_kv, w_kv)
        k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(k_r[:, None], (s, group, d_r))],
                            axis=-1)
        return q, k, kv[..., d_n:]

    def group_probs(q_rows, k, allowed_rows):
        logits = mm("qhd,khd->hqk", q_rows, k) * scale
        return jax.nn.softmax(jnp.where(allowed_rows, logits, -jnp.inf), axis=-1)

    @jax.checkpoint
    def attend(w_q, w_kv):
        """One group of heads: [S, group, d_v]."""
        q, k, v = group_qkv(w_q, w_kv)
        rows = jax.checkpoint(lambda q_rows, allowed_rows: mm(
            "hqk,khd->qhd", group_probs(q_rows, k, allowed_rows), v))
        out = jax.lax.map(lambda xs: rows(*xs),
                          (q.reshape(n_blocks, block, group, d_n + d_r), allowed_blocks))
        return out.reshape(s, group, d_v)

    attn = jnp.moveaxis(jax.lax.map(lambda xs: attend(*xs), (w_uq, w_ukv)), 0, 1)
    attn = attn.reshape(s, heads, d_v)
    seen = {}
    if indexed:
        # the target: attention's probabilities summed over ALL heads, a
        # constant; one more pass over the groups, with no gradient
        def summed(total, xs):
            q, k, _ = group_qkv(*xs)
            part = jax.lax.map(lambda ys: jnp.sum(group_probs(ys[0], k, ys[1]), axis=0),
                               (q.reshape(n_blocks, block, group, d_n + d_r), allowed_blocks))
            return total + part.reshape(s, s), None

        target, _ = jax.lax.scan(summed, jnp.zeros((s, s), jnp.float32),
                                 jax.lax.stop_gradient((w_uq, w_ukv)))
        target = jax.lax.stop_gradient(target / jnp.sum(target, axis=-1, keepdims=True))
        log_q = jax.nn.log_softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        kl = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-38)) - log_q), 0.0)
        seen = {"selection": own, "allowed": allowed,
                "index_loss": jnp.mean(jnp.sum(kl, axis=-1))}
    if spec["gate"]:
        attn = attn * jax.nn.sigmoid(mm("se,eh->sh", h, f32("w_attn_gate")))[..., None]
    return mm("shd,hde->se", attn, f32("wo")), seen


def _swiglu(h, w_gate, w_up, w_down):
    ff = (jax.nn.silu(mm("se,em->sm", h, w_gate.astype(jnp.float32)))
          * mm("se,em->sm", h, w_up.astype(jnp.float32)))
    return mm("sm,me->se", ff, w_down.astype(jnp.float32))


def dense_mlp(h, layer):
    h = h.astype(jnp.float32)
    rows = MLP_ROWS if h.shape[0] % MLP_ROWS == 0 else h.shape[0]
    part = jax.checkpoint(lambda x: _swiglu(x, layer["w_gate"], layer["w_up"], layer["w_down"]))
    return jax.lax.map(part, h.reshape(-1, rows, h.shape[1])).reshape(h.shape)


def expert_layer(h, layer, *, top_k: int, norm_topk: bool, first: int = 0):
    """The expert layer alone on h [S, E] (normed): (y [S, E], routing). The
    router scores all X experts; the ``count`` experts whose weights ``layer``
    holds are experts ``first .. first + count - 1``, each applied to every
    token and weighted by the token's gate for it (0 where it was not chosen);
    the shared expert is added plain. ``routing``: ``probs`` (the sigmoid
    scores), ``biased`` (what the choice ranks), ``chosen``, ``balance`` (this
    sequence's term)."""
    h = h.astype(jnp.float32)
    scores = jax.nn.sigmoid(mm("se,ex->sx", h, layer["router"].astype(jnp.float32)))
    biased = scores + jax.lax.stop_gradient(layer["router_bias"].astype(jnp.float32))
    chosen = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)[1]
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    n_experts = scores.shape[-1]
    picked = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)          # [S, k, X]
    weights = jnp.einsum("sk,skx->sx", gates, picked)
    count = layer["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    @jax.checkpoint
    def part(w_gate, w_up, w_down, weight):
        return weight[:, None] * _swiglu(h, w_gate, w_up, w_down)

    y, _ = jax.lax.scan(lambda y, xs: (y + part(*xs), None), jnp.zeros_like(h),
                        (layer["w_gate"], layer["w_up"], layer["w_down"], held.T))
    y = y + _swiglu(h, layer["w_shared_gate"], layer["w_shared_up"], layer["w_shared_down"])
    rows = jnp.sum(picked, axis=(0, 1))                                    # [X]
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    balance = jnp.sum(n_experts * rows / (h.shape[0] * top_k) * jnp.mean(share, axis=0))
    return y, {"probs": scores, "biased": biased, "chosen": chosen, "rows": rows,
               "balance": balance}


def bias_after(bias, rows, rate: float):
    """The selection bias after a step that routed ``rows`` [..., X] rows to
    each expert: ``b + rate sign(mean rows - rows)``."""
    rows = rows.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(rows, axis=-1, keepdims=True) - rows)


def block(x, layer, kind: str, lead: bool, arch: dict, key_set=None):
    """One decoder block on x [S, E] float32: (x, the mixer's ``seen``, the
    expert layer's ``routing`` or ``{}`` for a leading layer's dense MLP)."""
    eps = arch["norm_eps"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    y, seen = mla_mixer(h, layer, arch["kinds"][kind], eps, key_set)
    x = x + y
    h = _norm(x, layer["mlp_norm"].astype(jnp.float32), eps)
    if lead:
        return x + dense_mlp(h, layer), seen, {}
    y, routing = expert_layer(h, layer, top_k=arch["top_k"], norm_topk=arch["norm_topk"],
                              first=arch["held_first"])
    return x + y, seen, routing


def layers_of(params, arch: dict) -> list:
    """The model's blocks in order: (the block's leaves, its mixer's kind,
    whether it is a leading layer, where its leaves lie in ``params``:
    (``lead_layers``, ``layer<i>``) or (``layers``, ``slot<i>``, period))."""
    layers = [(params["lead_layers"][f"layer{i}"], kind, True, ("lead_layers", f"layer{i}"))
              for i, kind in enumerate(arch["lead_pattern"])]
    slots = params["layers"]
    for p in range(slots["slot0"]["attn_norm"].shape[0]):
        for i, kind in enumerate(arch["pattern"]):
            layers.append((jax.tree.map(lambda a: a[p], slots[f"slot{i}"]), kind, False,
                           ("layers", f"slot{i}", p)))
    return layers


def embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def head(x, final_norm, lm_head, eps: float):
    x = _norm(x, final_norm.astype(jnp.float32), eps)
    return mm("se,ev->sv", x, lm_head.astype(jnp.float32))


def logits(params, tokens, arch: dict, key_sets=None):
    """tokens [S] int32 -> (float32 logits [S, vocab], seen). ``arch``:
    ``kinds`` {mixer name: its spec}, ``pattern`` and ``lead_pattern`` (mixer
    names), ``norm_eps``, ``top_k``, ``norm_topk``, ``held_first``.
    ``key_sets`` [indexed layers, S, S]: the key sets to use, in layer order.
    ``seen``: ``selection`` and ``allowed`` [indexed layers, S, S], ``index_loss``
    [indexed layers], and the expert layers' ``routing`` stacked."""
    x = embed(params["embed"], tokens)
    mixers, routings, n_indexed = [], [], 0
    for layer, kind, lead, _ in layers_of(params, arch):
        key_set = None
        if arch["kinds"][kind].get("index_heads"):
            key_set = None if key_sets is None else key_sets[n_indexed]
            n_indexed += 1
        # a block is recomputed in a backward pass (its input alone is kept)
        x, seen, routing = jax.checkpoint(
            lambda x, layer, key_set, kind=kind, lead=lead: block(
                x, layer, kind, lead, arch, key_set))(x, layer, key_set)
        if seen:
            mixers.append(seen)
        if routing:
            routings.append(routing)
    out = head(x, params["final_norm"], params["lm_head"], arch["norm_eps"])
    seen = {"routing": jax.tree.map(lambda *a: jnp.stack(a), *routings)}
    if mixers:
        seen.update(jax.tree.map(lambda *a: jnp.stack(a), *mixers))
    return out, seen


def loss(params, rows, arch: dict, *, aux_weight: float, key_sets=None,
         return_seen: bool = False):
    """The training loss of token rows [B, S] (the module's text). ``key_sets``
    [B, indexed layers, S, S] or None. ``return_seen=True`` returns ``(loss,
    seen)`` for ``value_and_grad(has_aux=True)``: the first row's ``logits``,
    router ``probs`` and ``biased`` and ``selection``, the three terms (``ce``,
    ``balance``, ``index_loss``), ``selected_share`` and ``own_selected_share``
    (keys attended over causal keys) and ``rows_per_expert`` [expert layers, X]
    over all rows."""
    rows = jnp.asarray(rows)

    # a row at a time, recomputed whole in a backward pass
    def one(xs):
        row, key_set = xs if key_sets is not None else (xs, None)
        lg, seen = logits(params, row, arch, key_set)
        return loss_of(lg, row), seen, lg

    xs = rows if key_sets is None else (rows, key_sets)
    if rows.shape[0] == 1:
        # no loop over one row: a loop's backward pass keeps a second copy of
        # every weight's gradient, the sum over its turns
        ces, seen, lgs = jax.tree.map(lambda a: a[None], one(jax.tree.map(lambda a: a[0], xs)))
    else:
        ces, seen, lgs = jax.lax.map(jax.checkpoint(one), xs)
    ce = jnp.mean(ces)
    balance = jnp.mean(seen["routing"]["balance"])
    total = ce + aux_weight * balance
    out = {"logits": lgs[0], "probs": seen["routing"]["probs"][0],
           "biased": seen["routing"]["biased"][0], "ce": ce, "balance": balance,
           "rows_per_expert": jnp.sum(seen["routing"]["rows"], axis=0)}
    if "index_loss" in seen:
        index = jnp.mean(seen["index_loss"])
        total = total + index
        out.update(index_loss=index, selection=seen["selection"][0],
                   # keys over causal keys: of the sets attention used, and of
                   # the indexer's own (the same unless ``key_sets`` were given)
                   selected_share=jnp.mean(jnp.sum(seen["allowed"], axis=(-1, -2)))
                   / (rows.shape[1] * (rows.shape[1] + 1) / 2),
                   own_selected_share=jnp.mean(jnp.sum(seen["selection"], axis=(-1, -2)))
                   / (rows.shape[1] * (rows.shape[1] + 1) / 2))
    return (total, out) if return_seen else total
