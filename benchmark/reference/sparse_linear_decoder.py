"""Plain reference of a decoder of block-selected sparse attention beside
lightning linear attention under fixed multipliers (MiniCPM-SALA, from its
``config.json`` and, for what no key settles, the readings the configuration
file lists under ``assumed``). Straightforward ``jax.numpy``, float32, matmuls
at ``highest`` precision, no kernel, no chunked form: the recurrence is a scan
over positions, the selection a sort, and every query scores every key and
masks what it may not attend. Blocks of positions or of queries only so that
16k positions fit.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. The stack, ``a`` the residual
multiplier, x [S, E] the stream:

    x_0    = embed_scale embed(ids)
    x     += a mixer(n(x; w_attn));   x += a mlp(n(x; w_mlp))
    mlp(h) = (silu(h W_gate) * h W_up) W_down
    logits = (n(x_L; w_final) logit_scale) W_head

A lightning layer of published index l, head j = 1..H, h the normed input:

    q = rope(n_D(h W_q; w_q));  k = rope(n_D(h W_k; w_k));  v = h W_v
    S_t = lam S_{t-1} + k_t^T v_t;   o_t = D^-1/2 q_t S_t
    lam = exp(-2^(-8 j / H) (1 - l / (depth - 1) + 1e-5))
    y   = (n_HD(o; w_o) * sigmoid(h W_g)) W_o

Rope turns a head's features as split halves: pair i < D/2 is features (i,
i + D/2), by the angle ``t theta^(-2i/D)`` at position t.

A block-selected layer (no rope), H query heads over KV kv heads, group g the
heads of kv head g:

    q = n_D(h W_q; w_q);  k = n_D(h W_k; w_k);  v = h W_v
    Kp_i     = mean(k[stride i : stride i + size])
    p_j[t,.] = softmax_i(D^-1/2 q_j[t] . Kp_i)    over the i with stride i + size - 1 <= t
    P_g[t,i] = sum_{j in g} p_j[t, i]
    s_g[t,b] = max of P_g[t, i] over the i whose window meets keys block b ..
    B_g[t]   = block 0 (``init_blocks``), the blocks holding keys
               t - window + 1 .. t, and the best-scoring other visible blocks,
               ``topk`` in all: a sort by falling score, the earlier block first
               among equals
    o_j[t]   = softmax over the keys s <= t of the blocks B_g[t] of
               (D^-1/2 q_j[t] . k[s]) v[s]
    y        = (o * sigmoid(h W_g)) W_o

The selection is a constant (no gradient). ``block_sets`` hands a layer the
sets to attend in the place of its own (the comparisons hand over the
program's: a tie or a rounding at the last place flips a block); its own are
made and returned all the same, for the share of sets on which the two agree.

The weights are the program's own arrays read by the names of its parameter
tree (``layers/slot<i>/<leaf>`` stacked over the periods) and upcast to
float32; the decays are NOT read from it but made here from the layer's
published index. Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, loss_of, position_errors
from .latent_sparse_decoder import _norm, embed, layers_of

__all__ = ["logits", "loss", "loss_and_grads", "block", "lightning_mixer", "sparse_mixer",
           "select", "decays", "sets_agreement", "layers_of", "loss_of", "position_errors"]

# the precision of every product; the runner's control lowers it
PRECISION = [HIGHEST]


def mm(*args):
    return jnp.einsum(*args, precision=PRECISION[0])


# Query rows scored at a time, for one kv head's group of query heads: 16
# heads x 128 rows x 16,384 keys are 134 MB of float32 scores
QUERY_BLOCK = 128
# Positions of the recurrence kept between: a block's positions are run again
# in a backward pass from the state that entered it
SCAN_BLOCK = 128
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


def decays(spec: dict, layer_id: int) -> np.ndarray:
    """``lam`` [H] of the lightning layer whose published index is
    ``layer_id``."""
    j = np.arange(1, spec["heads"] + 1, dtype=np.float64)
    slopes = 2.0 ** (-8.0 * j / spec["heads"])
    return np.exp(-slopes * (1.0 - layer_id / (spec["depth"] - 1) + 1e-5)).astype(np.float32)


def recurrence(q, k, v, lam, *, state_dtype=jnp.float32):
    """q, k, v [S, H, D] float32, lam [H] -> o [S, H, D]: ``S_t = lam S_{t-1}
    + k_t^T v_t``, ``o_t = q_t S_t``, position by position."""
    s, h, d = q.shape
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    lam = jnp.asarray(lam, jnp.float32)[:, None, None]

    def position(state, xs):
        q_t, k_t, v_t = xs
        state = (lam * state.astype(jnp.float32) + mm("hk,hv->hkv", k_t, v_t)).astype(state_dtype)
        return state, mm("hk,hkv->hv", q_t, state.astype(jnp.float32))

    @jax.checkpoint
    def positions(state, xs):
        return jax.lax.scan(position, state, xs)

    blocks = lambda x: x.reshape(s // block, block, h, d)  # noqa: E731
    _, o = jax.lax.scan(positions, jnp.zeros((h, d, d), state_dtype),
                        (blocks(q), blocks(k), blocks(v)))
    return o.reshape(s, h, d)


def lightning_mixer(h, layer, spec: dict, layer_id: int, eps: float, *, state_dtype=jnp.float32):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``head_dim``,
    ``rope_theta``, ``depth``."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s, d = h.shape[0], spec["head_dim"]
    q = _rope(_norm(mm("se,ehd->shd", h, f32("wq")), f32("q_norm"), eps), spec["rope_theta"])
    k = _rope(_norm(mm("se,ehd->shd", h, f32("wk")), f32("k_norm"), eps), spec["rope_theta"])
    v = mm("se,ehd->shd", h, f32("wv"))
    o = recurrence(q, k, v, decays(spec, layer_id), state_dtype=state_dtype) / math.sqrt(d)
    o = _norm(o.reshape(s, -1), f32("o_norm"), eps).reshape(o.shape)
    o = o * jax.nn.sigmoid(mm("se,ehd->shd", h, f32("w_attn_gate")))
    return mm("shd,hde->se", o, f32("wo"))


def select(q, k, spec: dict):
    """q [S, H, D], k [S, KV, D] float32 -> the block sets [KV, S, NB] bool
    (NB = ceil(S / block_size)), by a sort."""
    s, heads, d = q.shape
    kv = k.shape[1]
    size, stride, bsz = spec["kernel_size"], spec["kernel_stride"], spec["block_size"]
    n_pool, n_blocks = max((s - size) // stride + 1, 0), -(-s // bsz)
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    t = np.arange(s)
    visible = np.arange(n_blocks)[None, :] <= (t // bsz)[:, None]                # [S, NB]
    forced = visible & ((np.arange(n_blocks)[None, :] < spec["init_blocks"])
                        | (np.arange(n_blocks)[None, :]
                           >= (np.maximum(t - (spec["window_size"] - 1), 0) // bsz)[:, None]))
    if n_pool == 0:
        return jnp.broadcast_to(jnp.asarray(visible), (kv, s, n_blocks))
    starts = np.arange(n_pool) * stride
    pooled = jnp.stack([k[a:a + size].mean(axis=0) for a in starts])             # [P, KV, D]
    seen = (starts + size - 1)[None, :] <= t[:, None]                            # [S, P]
    # pooled key i meets block b: their key ranges overlap
    meets = ((starts + size - 1)[None, :] >= (np.arange(n_blocks) * bsz)[:, None]) & (
        starts[None, :] <= (np.arange(n_blocks) * bsz + bsz - 1)[:, None])       # [NB, P]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def rows(xs):
        q_rows, seen_rows, forced_rows, visible_rows = xs       # [Q, H, D], [Q, P], [Q, NB] x 2
        scores = mm("qkgd,pkd->kgqp", q_rows.reshape(block, kv, heads // kv, d),
                    pooled) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen_rows, scores, -jnp.inf), axis=-1)
        group = jnp.where(seen_rows, probs, 0.0).sum(axis=1)                     # [KV, Q, P]
        score = jnp.max(jnp.where(meets, group[:, :, None, :], 0.0), axis=-1)    # [KV, Q, NB]
        score = jnp.where(forced_rows, jnp.inf, jnp.where(visible_rows, score, -jnp.inf))
        _, best = jax.lax.top_k(score, min(spec["topk"], n_blocks))  # the earlier of equals first
        chosen = jax.nn.one_hot(best, n_blocks, dtype=jnp.bool_).any(axis=-2)
        return chosen & visible_rows

    parts = lambda a: jnp.asarray(a).reshape((s // block, block) + a.shape[1:])  # noqa: E731
    sets = jax.lax.map(rows, (parts(q), parts(seen), parts(forced), parts(visible)))
    return jnp.moveaxis(sets, 0, 1).reshape(kv, s, n_blocks)


def sparse_mixer(h, layer, spec: dict, eps: float, block_sets=None):
    """h [S, E] (normed) -> (y [S, E], the layer's OWN sets [KV, S, NB]).
    ``spec``: ``heads``, ``kv_heads``, ``head_dim`` and the selection's six
    sizes. ``block_sets`` [KV, S, >= NB]: the sets to attend, in the place of
    its own."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, kv_heads, d, bsz = spec["heads"], spec["kv_heads"], spec["head_dim"], spec["block_size"]
    group = heads // kv_heads
    q = _norm(mm("se,ehd->shd", h, f32("wq")), f32("q_norm"), eps)
    k = _norm(mm("se,ehd->shd", h, f32("wk")), f32("k_norm"), eps)
    v = mm("se,ehd->shd", h, f32("wv"))
    own = select(q, k, spec)
    sets = own if block_sets is None else jnp.asarray(block_sets)[:, :s, :own.shape[-1]] != 0
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def one_kv_head(xs):
        q_j, k_j, v_j, sets_j = xs                      # [S, g, D], [S, D], [S, D], [S, NB]

        @jax.checkpoint
        def rows(q_rows, set_rows, first):
            scores = mm("qgd,kd->gqk", q_rows, k_j) / math.sqrt(d)
            allowed = ((first + jnp.arange(block))[:, None] >= keys[None, :]) & jnp.repeat(
                set_rows, bsz, axis=-1)[:, :s]
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
            return mm("gqk,kd->qgd", probs, v_j)

        out = jax.lax.map(lambda xs: rows(*xs), (
            q_j.reshape(s // block, block, group, d),
            sets_j.reshape(s // block, block, -1), jnp.arange(0, s, block)))
        return out.reshape(s, group, d)

    attn = jax.lax.map(jax.checkpoint(one_kv_head),
                       (q.reshape(s, kv_heads, group, d).swapaxes(0, 1),
                        k.swapaxes(0, 1), v.swapaxes(0, 1), sets))      # [KV, S, g, D]
    attn = attn.swapaxes(0, 1).reshape(s, heads, d)
    attn = attn * jax.nn.sigmoid(mm("se,ehd->shd", h, f32("w_attn_gate")))
    return mm("shd,hde->se", attn, f32("wo")), own


def sets_agreement(own, given) -> dict:
    """How far a layer's own sets [KV, S, NB] agree with the ones it was
    given: the share of (group, query) sets equal in every block, and the
    share of single block flags that are equal."""
    given = np.asarray(given)[:, :own.shape[1], :own.shape[2]] != 0
    same = np.asarray(own) == given
    return {"sets": float(same.all(axis=-1).mean()), "flags": float(same.mean())}


def _mlp(h, layer):
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    ff = jax.nn.silu(mm("se,em->sm", h, f32("w_gate"))) * mm("se,em->sm", h, f32("w_up"))
    return mm("sm,me->se", ff, f32("w_down"))


def block(x, layer, kind: str, layer_id: int, arch: dict, block_sets=None):
    """One decoder block on x [S, E] float32: (x, the layer's own sets or
    None)."""
    eps, a = arch["norm_eps"], arch["residual_scale"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    if kind == "lightning":
        mixed, own = lightning_mixer(h, layer, arch["kinds"][kind], layer_id, eps), None
    else:
        mixed, own = sparse_mixer(h, layer, arch["kinds"][kind], eps, block_sets)
    x = x + a * mixed
    return x + a * _mlp(_norm(x, layer["mlp_norm"].astype(jnp.float32), eps), layer), own


def head(x, final_norm, lm_head, arch: dict):
    x = _norm(x, final_norm.astype(jnp.float32), arch["norm_eps"]) * arch["logit_scale"]
    return mm("se,ev->sv", x, lm_head.astype(jnp.float32))


def _stack(params, arch: dict, block_sets):
    """The blocks in order: (where the leaves lie, kind, published index, the
    sets handed to it or None)."""
    out, n_sparse = [], 0
    for i, (_, kind, _, where) in enumerate(layers_of(params, arch)):
        given = None
        if kind == "block_sparse":
            given = None if block_sets is None else block_sets[n_sparse]
            n_sparse += 1
        out.append((where, kind, arch["layer_ids"][i], given))
    return out


def _leaves(params, where):
    return jax.tree.map(lambda a: a[where[2]], params[where[0]][where[1]])


def logits(params, tokens, arch: dict, block_sets=None):
    """tokens [S] int32 -> (float32 logits [S, vocab], the block-selected
    layers' own sets [those layers, KV, S, NB]). ``arch``: ``kinds`` {mixer
    name: its spec}, ``pattern`` (mixer names of a period; ``lead_pattern``
    is ()), ``layer_ids`` (the published index of every layer), ``norm_eps``
    and the three multipliers. ``block_sets`` [those layers, KV, S, NB]: the
    sets to attend, in layer order."""
    x = embed(params["embed"], tokens) * arch["embed_scale"]
    owns = []
    for where, kind, layer_id, given in _stack(params, arch, block_sets):
        # a block is recomputed in a backward pass (its input alone is kept)
        x, own = jax.checkpoint(
            lambda x, layer, given, kind=kind, layer_id=layer_id: block(
                x, layer, kind, layer_id, arch, given))(x, _leaves(params, where), given)
        if own is not None:
            owns.append(own)
    return head(x, params["final_norm"], params["lm_head"], arch), jnp.stack(owns)


def loss(params, rows, arch: dict, block_sets=None):
    """Mean next-token cross entropy of token rows [B, S]; ``block_sets``
    [B, those layers, KV, S, NB]."""
    rows = jnp.asarray(rows)
    each = [loss_of(logits(params, rows[b], arch,
                           None if block_sets is None else block_sets[b])[0], rows[b])
            for b in range(rows.shape[0])]
    return jnp.mean(jnp.stack(each))


def loss_and_grads(params, rows, arch: dict, block_sets=None) -> tuple:
    """``loss`` and its gradient, a BLOCK at a time: (loss, seen, {leaf path as
    ``jax.tree_util.keystr`` prints it: the gradient in the leaf's own type}).
    ``seen``: the first row's ``logits`` (on the host) and ``own_sets`` [those
    layers, KV, S, NB] (on the host), and ``ce``.

    A row at a time: a forward pass keeps each block's input, the head gives
    the gradient of its own leaves and of the last hidden state, and each
    block's own ``jax.vjp`` is run under one ``jit`` a kind of block and
    published index. One block's float32 weights, their cotangents and its
    activations are on the device at a time. The same numbers as ``jax.grad``
    of ``loss`` (a test holds them equal)."""
    rows = jnp.asarray(rows)
    n_rows = rows.shape[0]

    @functools.lru_cache(maxsize=None)
    def forward(kind, layer_id):
        return jax.jit(lambda x, layer, given: block(x, layer, kind, layer_id, arch, given))

    @functools.lru_cache(maxsize=None)
    def backward(kind, layer_id):
        def pull(x, layer, given, ct):
            return jax.vjp(lambda x, layer: block(x, layer, kind, layer_id, arch, given)[0],
                           x, layer)[1](ct)

        return jax.jit(pull)

    @jax.jit
    def head_terms(x, final_norm, lm_head, row):
        """The row's share of the cross entropy and its gradient with respect
        to (x, final_norm, lm_head), HEAD_ROWS positions at a time. Sums in
        float32, rounded once to a leaf's type."""
        s, e = x.shape
        rows_at_once = HEAD_ROWS if s % HEAD_ROWS == 0 else s
        weight = (jnp.arange(s) < s - 1) / ((s - 1) * n_rows)   # the last position has no target
        norm32, head32 = final_norm.astype(jnp.float32), lm_head.astype(jnp.float32)

        def nll(x, norm32, head32, targets, weight):
            lg = head(x, norm32, head32, arch)
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

    scatter = jax.jit(lambda ct, row: (
        jnp.zeros(params["embed"].shape, jnp.float32).at[row].add(ct) * arch["embed_scale"]
    ).astype(params["embed"].dtype))
    add = lambda a, b: b if a is None else jax.tree.map(  # noqa: E731
        lambda x, y: (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype), a, b)
    grads = {"embed": None, "final_norm": None, "lm_head": None}
    first_row, ce, by_block = {}, 0.0, None
    for b in range(n_rows):
        row = rows[b]
        stack = _stack(params, arch, None if block_sets is None else block_sets[b])
        by_block = by_block or [None] * len(stack)
        xs = [jax.jit(lambda t, r: embed(t, r) * arch["embed_scale"])(params["embed"], row)]
        owns = []
        for where, kind, layer_id, given in stack:
            x, own = forward(kind, layer_id)(
                xs[-1], _leaves(params, where), given)
            xs.append(x)
            if own is not None:
                owns.append(np.asarray(own))
        if b == 0:  # on the host: the backward pass needs the room
            lg = jax.jit(lambda x, n, w: head(x, n, w, arch))(
                xs[-1], params["final_norm"], params["lm_head"])
            first_row = {"logits": np.asarray(lg), "own_sets": np.stack(owns)}
            del lg
        row_ce, (ct, d_norm, d_head) = head_terms(
            xs[-1], params["final_norm"], params["lm_head"], row)
        ce += float(row_ce)
        grads["final_norm"] = add(grads["final_norm"], d_norm)
        grads["lm_head"] = add(grads["lm_head"], d_head)
        for i in reversed(range(len(stack))):
            where, kind, layer_id, given = stack[i]
            ct, d_layer = backward(kind, layer_id)(
                xs[i], _leaves(params, where), given, ct)
            by_block[i] = add(by_block[i], d_layer)
            xs.pop()
        grads["embed"] = add(grads["embed"], scatter(ct, row))
    # the blocks' gradients back under the leaves' own names, stacked over the periods
    by_name = {f"['{k}']": grads[k] for k in ("embed", "final_norm", "lm_head")}
    periods = {}
    for (where, _, _, _), d_layer in zip(stack, by_block):
        for leaf, g in d_layer.items():
            periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: jnp.stack(gs) for name, gs in periods.items()})
    return ce, {**first_row, "ce": ce}, by_name
