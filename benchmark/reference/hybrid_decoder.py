"""Plain reference of a hybrid decoder: Gated DeltaNet layers beside gated
softmax attention in the pattern L L L F, every block followed by a routed
expert MLP with a shared expert (Qwen3-Next-80B-A3B, from its ``config.json``
and the published implementation). Straightforward ``jax.numpy``, float32,
matmuls at ``highest`` precision, no kernel, no chunking, no sort, no
dispatch: the delta rule runs token by token as a ``lax.scan``, and EVERY
expert held here is applied to every token and masked by the top-k choice.

Norm everywhere unless said: ``n(x; w) = x rsqrt(mean(x^2) + eps) (1 + w)``.
Layer i (0-based) is attention iff ``(i + 1) % period == 0``. A block:
``x += mixer(n(x; w_in)); x += experts(n(x; w_post))``.

Gated DeltaNet mixer (KH key heads, VH value heads of D; h the normed input):

    q, k, v, z = split(h W_qkvz);  b, a = split(h W_ba)
    [q; k; v]  = silu(causal depthwise conv1d([q; k; v], width 4, no bias))
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    q = l2norm(q) D^-0.5, k = l2norm(k) per head; key head j serves value
    heads j VH/KH .. (j + 1) VH/KH - 1
    per value head, S [D, D] from zero:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T;  o_t = S^T q_t
    o = (o rsqrt(mean(o^2 over the head) + eps) w_norm) silu(z)   (times w, not 1 + w)
    y = o W_out

Gated attention mixer (H q heads, KV kv heads of D): ``q | gate = h W_q``
viewed [H, 2 D] and split per head; ``k = h W_k``, ``v = h W_v``; q and k
normed per head over D with (1 + w); rope (split halves) on the first
``rotary_dim`` features of q and k; causal softmax attention at scale
D^-0.5; ``y = (attn sigmoid(gate)) W_o``.

Expert layer: ``p = softmax(h W_r)`` over all X experts; top-k; gates
divided by their sum if ``norm_topk``; ``y = sum_j g_j W_down[e_j]
(silu(W_gate[e_j] h) W_up[e_j] h)`` over the chosen experts AMONG THOSE HELD
(``held`` = (first, count): the weights given are those experts', a chip's
share of an expert-parallel deployment; what absent experts would add is
left out, here as in the program), plus ``sigmoid(h w_sg) shared(h)``,
``shared`` a SwiGLU. The load-balancing term is over all X experts.

Departures from the published model, each also in the configuration file:
the fused projections' columns are laid out q | k | v | z and b | a whole,
where the published weights interleave them per key head (with seeded
weights the layouts are one distribution); no multi-token-prediction head
(the source's ``config.json`` has no key for one); the vocabulary is a
slice. The weights are the program's own bf16-rounded arrays, read by the
names of its parameter tree (``layers/slot<i>/<leaf>``, stacked over the
periods) and upcast to float32, an expert at a time. Independent of
``ray_tpu``: nothing is imported from it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense_decoder import HIGHEST, loss_of, position_errors
from .moe_decoder import aux_losses

__all__ = ["logits", "loss", "gdn_mixer", "attn_mixer", "expert_layer", "delta_rule",
           "aux_losses", "loss_of", "position_errors"]

mm = functools.partial(jnp.einsum, precision=HIGHEST)


def _norm(x, w, eps, offset=1.0):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (offset + w)


def _rope(x, theta, rotary_dim):
    """x [S, H, D]: rotate the first ``rotary_dim`` features, positions 0..S-1."""
    s, d = x.shape[0], rotary_dim
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2, rest = x[..., : d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], axis=-1)


# Under ``jax.grad`` a scan keeps what each step needs of its carry: 2 MB a
# token for 32 states of [128, 128], 17 GB at 8,192 tokens. The same steps
# run as a scan of scans, the inner one recomputed in the backward pass
# (``jax.checkpoint``), so only a state every RULE_BLOCK tokens is kept. The
# recurrence is still token by token; forward, nothing differs.
RULE_BLOCK = 64
# Query rows of attention scored at a time (``attn_mixer``): all 8,192 at
# once are 4.3 GB of float32 scores. Each block is the plain softmax over
# all its keys, no running maximum, recomputed in the backward pass.
QUERY_BLOCK = 1024


@jax.jit
def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token: q, k [H, S, D], v [H, S, Dv], g,
    beta [H, S] -> o [H, S, Dv], float32."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t) * state
        d_t = b_t * (v_t - mm("kv,k->v", state, k_t))
        state = state + k_t[:, None] * d_t[None, :]
        return state, mm("kv,k->v", state, q_t)

    def head(q, k, v, g, beta):
        s = q.shape[0]
        block = RULE_BLOCK if s % RULE_BLOCK == 0 else s
        blocks = jax.tree.map(lambda t: t.reshape((s // block, block) + t.shape[1:]),
                              (q, k, v, g, beta))
        zero = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        out = jax.lax.scan(
            jax.checkpoint(lambda state, xs: jax.lax.scan(step, state, xs)), zero, blocks)[1]
        return out.reshape(s, v.shape[-1])

    return jax.vmap(head)(f32(q), f32(k), f32(v), f32(g), f32(beta))


@functools.partial(jax.jit, static_argnames=("key_heads", "value_heads", "eps"))
def gdn_mixer(h, layer, *, key_heads: int, value_heads: int, eps: float):
    """h [S, E] (normed) -> (y [S, E], the rule's operands and output)."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    d = layer["gdn_norm"].shape[-1]
    kw, vw = key_heads * d, value_heads * d
    qkvz = mm("se,ef->sf", h, f32("w_qkvz"))
    ba = mm("se,ef->sf", h, f32("w_ba"))
    qkv, z = qkvz[:, :2 * kw + vw], qkvz[:, 2 * kw + vw:]
    conv_w = f32("conv_w")                                    # [C, W]
    width = conv_w.shape[1]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + s] * conv_w[:, j] for j in range(width)))
    beta = jax.nn.sigmoid(ba[:, :value_heads])
    g = -jnp.exp(f32("A_log")) * jax.nn.softplus(ba[:, value_heads:] + f32("dt_bias"))
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(qkv[:, :kw].reshape(s, key_heads, d)) * d ** -0.5
    k = l2(qkv[:, kw:2 * kw].reshape(s, key_heads, d))
    v = qkv[:, 2 * kw:].reshape(s, value_heads, d)
    rep = value_heads // key_heads
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
    heads_first = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    o = delta_rule(heads_first(q), heads_first(k), heads_first(v), g.T, beta.T)
    seen = {"q": heads_first(q), "k": heads_first(k), "v": heads_first(v),
            "g": g.T, "beta": beta.T, "o": o}
    o = heads_first(o)                                        # [S, VH, D]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * f32("gdn_norm")
    o = o.reshape(s, vw) * jax.nn.silu(z)
    return mm("sf,fe->se", o, f32("w_out")), seen


@functools.partial(jax.jit, static_argnames=("theta", "eps", "rotary_dim"))
def attn_mixer(h, layer, *, theta: float, eps: float, rotary_dim: int):
    """h [S, E] (normed) -> y [S, E]: gated softmax attention."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    d = layer["wk"].shape[-1]
    qg = mm("se,ehd->shd", h, f32("wq"))                      # [S, H, 2 D]
    q, gate = qg[..., :d], qg[..., d:]
    k = mm("se,ehd->shd", h, f32("wk"))
    v = mm("se,ehd->shd", h, f32("wv"))
    q, k = _norm(q, f32("q_norm"), eps), _norm(k, f32("k_norm"), eps)
    q, k = _rope(q, theta, rotary_dim), _rope(k, theta, rotary_dim)
    s, heads, _ = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def rows(q_rows, first):
        scores = mm("qhd,khd->hqk", q_rows, k) / math.sqrt(d)
        causal = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", probs, v)

    attn = jax.lax.map(lambda xs: rows(*xs), (q.reshape(s // block, block, heads, d),
                                              jnp.arange(0, s, block)))
    attn = attn.reshape(s, heads, d) * jax.nn.sigmoid(gate)
    return mm("shd,hde->se", attn, f32("wo"))


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk", "first"))
def expert_layer(h, layer, *, top_k: int, norm_topk: bool, first: int = 0):
    """The expert layer alone on h [S, E] (normed): (y [S, E], routing). The
    router scores all X experts; the ``count`` experts whose weights
    ``layer`` holds are experts ``first .. first + count - 1``, each applied
    to every token and weighted by the token's gate for it (0 where it was
    not chosen); the shared expert is added where ``layer`` has one."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    router_logits = mm("se,ex->sx", h, f32("router"))
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32)  # [S, k, X]
    weights = jnp.einsum("sk,skx->sx", gates, picked)
    count = layer["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)   # [S, count]

    def swiglu(w_gate, w_up, w_down):
        ff = (jax.nn.silu(mm("se,em->sm", h, w_gate.astype(jnp.float32)))
              * mm("se,em->sm", h, w_up.astype(jnp.float32)))
        return mm("sm,me->se", ff, w_down.astype(jnp.float32))

    # an expert's weighted part is recomputed in a backward pass: kept, the
    # 64 experts' activations and outputs on every token are 7.5 GB a layer
    # at 8,192 tokens
    @jax.checkpoint
    def part(w_gate, w_up, w_down, weight):
        return weight[:, None] * swiglu(w_gate, w_up, w_down)

    def one_expert(y, xs):
        return y + part(*xs), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (layer["w_gate"], layer["w_up"], layer["w_down"], held.T))
    if "w_shared_gate" in layer:
        scale = jax.nn.sigmoid(mm("se,e->s", h, f32("w_shared_scale")))
        y = y + scale[:, None] * swiglu(layer["w_shared_gate"], layer["w_shared_up"],
                                        layer["w_shared_down"])
    return y, {"probs": probs, "chosen": chosen,
               "lse": jax.nn.logsumexp(router_logits, axis=-1)}


def _block(x, layer, kind: str, arch: dict):
    eps = arch["norm_eps"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    if kind == "gdn":
        y, _ = gdn_mixer(h, layer, key_heads=arch["key_heads"],
                         value_heads=arch["value_heads"], eps=eps)
    else:
        y = attn_mixer(h, layer, theta=arch["rope_theta"], eps=eps,
                       rotary_dim=arch["rotary_dim"])
    x = x + y
    h = _norm(x, layer["mlp_norm"].astype(jnp.float32), eps)
    y, routing = expert_layer(h, layer, top_k=arch["top_k"], norm_topk=arch["norm_topk"],
                              first=arch["held_first"])
    return x + y, routing


def logits(params, tokens, **arch):
    """tokens [S] int32 -> (float32 logits [S, vocab], routing): position i
    scores token i+1 given tokens 0..i; ``routing`` holds ``probs``
    [L, S, X], ``chosen`` [L, S, k] and ``lse`` [L, S]. ``arch``:
    ``pattern`` (the mixers of a period), ``rope_theta``, ``rotary_dim``,
    ``norm_eps``, ``key_heads``, ``value_heads``, ``top_k``, ``norm_topk``,
    ``held_first``."""
    x = params["embed"][tokens].astype(jnp.float32)
    pattern = arch["pattern"]
    n_periods = params["layers"]["slot0"]["attn_norm"].shape[0]
    routings = []
    for p in range(n_periods):
        for i, kind in enumerate(pattern):
            layer = jax.tree.map(lambda a: a[p], params["layers"][f"slot{i}"])
            # a block is recomputed in a backward pass (its input alone is kept)
            x, routing = jax.checkpoint(
                lambda x, layer, kind=kind: _block(x, layer, kind, arch))(x, layer)
            routings.append(routing)
    x = _norm(x, params["final_norm"].astype(jnp.float32), arch["norm_eps"])
    out = mm("se,ev->sv", x, params["lm_head"].astype(jnp.float32))
    return out, jax.tree.map(lambda *a: jnp.stack(a), *routings)


def loss(params, rows, *, aux_weight: float, return_seen: bool = False, **arch):
    """The training loss of token rows [B, S]: mean next-token cross entropy
    over all rows plus the weighted load-balancing term (the mean over
    layers, over all the rows' tokens, over all X experts).
    ``return_seen=True`` returns ``(loss, seen)`` for ``value_and_grad(
    has_aux=True)``: the first row's ``logits`` and router ``probs``, and the
    two terms (``ce``, ``load_balance``)."""
    # a row at a time (``lax.map``), recomputed whole in a backward pass: two
    # rows' passes side by side are 12 GB at 8,192 tokens
    def one(row):
        lg, routing = logits(params, row, **arch)
        return loss_of(lg, row), routing, lg

    rows = jnp.asarray(rows)
    ces, stacked, lgs = jax.lax.map(jax.checkpoint(one), rows)
    routings = [jax.tree.map(lambda a: a[i], stacked) for i in range(rows.shape[0])]
    balance, _ = aux_losses(routings)
    ce = jnp.mean(ces)
    total = ce + aux_weight * balance
    if not return_seen:
        return total
    return total, {"logits": lgs[0], "probs": routings[0]["probs"], "ce": ce,
                   "load_balance": balance}
