"""Plain reference of a decoder of Mamba-2 state-space layers beside un-roped
grouped-query attention under Granite's four multipliers and a tied vocabulary
(granite-4.0-h-micro, ``model_type`` ``granitemoehybrid``, from its
``config.json`` and, for what no key settles, the readings the configuration
file lists under ``assumed``). Straightforward ``jax.numpy``, float32, matmuls
at ``highest`` precision, no kernel, no chunked form: the recurrence is a scan
over positions, and every query scores every key and masks what it may not
attend. Blocks of positions or of queries only so that 32k positions fit.

Norm: ``n(x; w) = x rsqrt(mean(x^2) + eps) w``. The stack, ``a`` the residual
multiplier, x [S, E] the stream, E the ONE table [V, E]:

    x_0    = embed_scale E[ids]
    x     += a mixer(n(x; w_attn));   x += a mlp(n(x; w_mlp))
    mlp(h) = (silu(h W_gate) * h W_up) W_down
    logits = (n(x_L; w_final) logit_scale) E^T

A state-space layer, H heads of P features over a state of N, h the normed
input:

    z = h W_z;  x = h W_x;  [B | C] = h W_bc;  dt = h W_dt
    [x | B | C]_t = silu(b + sum_i taps[i] [x | B | C]_{t-(K-1)+i})    zeros before the row
    dt = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
    out = n_{HP}(y * silu(z); w) W_out

An attention layer (no rope), H query heads over KV kv heads of D:

    o_j[t] = softmax over the keys s <= t of (scale q_j[t] . k[s]) v[s],  scale a constant
    out    = o W_o

Departures from the published class, each without a number of its own: W_in and
the conv are held as their column blocks (z | x | B, C | dt), the same numbers;
``time_step_limit`` is (0, inf), so dt is not clipped; the scan has no chunk.

The weights are the program's own arrays read by the names of its parameter
tree (``layers/slot<i>/<leaf>`` stacked over the periods) and upcast to
float32. Independent of ``ray_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import HIGHEST, loss_of, position_errors
from .latent_sparse_decoder import _norm, embed, layers_of

__all__ = ["logits", "loss", "loss_and_grads", "block", "mamba_mixer", "attention_mixer",
           "recurrence", "layers_of", "loss_of", "position_errors"]

# the precision of every product; the runner's control lowers it
PRECISION = [HIGHEST]


def mm(*args):
    return jnp.einsum(*args, precision=PRECISION[0])


# Query rows scored at a time, for one kv head's group of query heads: 4 heads
# x 256 rows x 32,768 keys are 134 MB of float32 scores
QUERY_BLOCK = 256
# Positions of the recurrence kept between: a block's positions are run again
# in a backward pass from the state that entered it
SCAN_BLOCK = 128
# Positions whose logits ``loss_and_grads`` makes at a time
HEAD_ROWS = 2048


def recurrence(x, dt, a, bm, cm, d, *, state_dtype=jnp.float32):
    """x [S, H, P], dt [S, H], a and d [H], bm and cm [S, N], all float32 ->
    y [S, H, P]: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
    C_t + d x_t``, position by position."""
    s, h, p = x.shape
    n = bm.shape[-1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state.astype(jnp.float32)
                 + mm("hp,n->hpn", x_t * dt_t[:, None], b_t)).astype(state_dtype)
        return state, mm("hpn,n->hp", state.astype(jnp.float32), c_t) + d[:, None] * x_t

    @jax.checkpoint
    def positions(state, rows):
        return jax.lax.scan(position, state, rows)

    blocks = lambda v: v.reshape((s // block, block) + v.shape[1:])  # noqa: E731
    _, y = jax.lax.scan(positions, jnp.zeros((h, p, n), state_dtype),
                        (blocks(x), blocks(dt), blocks(bm), blocks(cm)))
    return y.reshape(s, h, p)


def _conv(x, taps, bias):
    """x [S, ...], taps [K, ...], bias [...]: the causal depthwise conv."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0),) + ((0, 0),) * (x.ndim - 1))
    return bias + sum(taps[i] * padded[i:i + s] for i in range(k))


def mamba_mixer(h, layer, spec: dict, eps: float, *, gate_inside: bool = True):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``head_dim``,
    ``state``, ``conv``. ``gate_inside`` False is a fault a comparison must
    refuse: the norm first, then the gate (the runner's control)."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    z = mm("se,ehp->shp", h, f32("w_z"))
    x = jax.nn.silu(_conv(mm("se,ehp->shp", h, f32("w_x")), f32("conv_x"), f32("conv_x_bias")))
    bc = jax.nn.silu(_conv(mm("se,egn->sgn", h, f32("w_bc")), f32("conv_bc"),
                           f32("conv_bc_bias")))
    dt = jax.nn.softplus(mm("se,eh->sh", h, f32("w_dt")) + f32("dt_bias"))
    y = recurrence(x, dt, -jnp.exp(f32("a_log")), bc[:, 0], bc[:, 1], f32("d_skip"))
    gate = jax.nn.silu(z)
    if gate_inside:
        g = _norm((y * gate).reshape(s, -1), f32("ssm_norm"), eps)
    else:
        g = _norm(y.reshape(s, -1), f32("ssm_norm"), eps) * gate.reshape(s, -1)
    return mm("shp,hpe->se", g.reshape(y.shape), f32("w_out"))


def attention_mixer(h, layer, spec: dict):
    """h [S, E] (normed) -> y [S, E]. ``spec``: ``heads``, ``kv_heads``,
    ``head_dim``, ``softmax_scale``; no rope."""
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    h = h.astype(jnp.float32)
    s = h.shape[0]
    heads, kv_heads, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    group = heads // kv_heads
    q = mm("se,ehd->shd", h, f32("wq"))
    k = mm("se,ehd->shd", h, f32("wk"))
    v = mm("se,ehd->shd", h, f32("wv"))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def one_kv_head(xs):
        q_j, k_j, v_j = xs                                  # [S, g, D], [S, D], [S, D]

        @jax.checkpoint
        def rows(q_rows, first):
            scores = mm("qgd,kd->gqk", q_rows, k_j) * spec["softmax_scale"]
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


def _mlp(h, layer):
    f32 = lambda name: layer[name].astype(jnp.float32)  # noqa: E731
    ff = jax.nn.silu(mm("se,em->sm", h, f32("w_gate"))) * mm("se,em->sm", h, f32("w_up"))
    return mm("sm,me->se", ff, f32("w_down"))


def block(x, layer, kind: str, arch: dict):
    """One decoder block on x [S, E] float32."""
    eps, a = arch["norm_eps"], arch["residual_scale"]
    h = _norm(x, layer["attn_norm"].astype(jnp.float32), eps)
    if kind == "mamba2":
        mixed = mamba_mixer(h, layer, arch["kinds"][kind], eps,
                            gate_inside=arch.get("gate_inside", True))
    else:
        mixed = attention_mixer(h, layer, arch["kinds"][kind])
    x = x + a * mixed
    return x + a * _mlp(_norm(x, layer["mlp_norm"].astype(jnp.float32), eps), layer)


def head(x, final_norm, table, arch: dict):
    """The tied head: the final norm's output times ``logit_scale`` against the
    embedding's own rows [V, E]."""
    x = _norm(x, final_norm.astype(jnp.float32), arch["norm_eps"]) * arch["logit_scale"]
    return mm("se,ve->sv", x, table.astype(jnp.float32))


def _leaves(params, where):
    return jax.tree.map(lambda a: a[where[2]], params[where[0]][where[1]])


def logits(params, tokens, arch: dict):
    """tokens [S] int32 -> float32 logits [S, vocab]. ``arch``: ``kinds`` {mixer
    name: its spec}, ``pattern`` (mixer names of a period; ``lead_pattern`` is
    ()), ``norm_eps`` and the three multipliers (``gate_inside`` False: the
    control, see ``mamba_mixer``)."""
    x = embed(params["embed"], tokens) * arch["embed_scale"]
    for _, kind, _, where in layers_of(params, arch):
        # a block is recomputed in a backward pass (its input alone is kept)
        x = jax.checkpoint(lambda x, layer, kind=kind: block(x, layer, kind, arch))(
            x, _leaves(params, where))
    return head(x, params["final_norm"], params["embed"], arch)


def loss(params, rows, arch: dict):
    """Mean next-token cross entropy of token rows [B, S]."""
    rows = jnp.asarray(rows)
    return jnp.mean(jnp.stack([loss_of(logits(params, rows[b], arch), rows[b])
                               for b in range(rows.shape[0])]))


def loss_and_grads(params, rows, arch: dict) -> tuple:
    """``loss`` and its gradient, a BLOCK at a time: (loss, seen, {leaf path as
    ``jax.tree_util.keystr`` prints it: the gradient in the leaf's own type}).
    ``seen``: the first row's ``logits`` (on the host) and ``ce``.

    A row at a time: a forward pass keeps each block's input, the head gives
    the gradient of its own leaves and of the last hidden state, and each
    block's own ``jax.vjp`` is run under one ``jit`` a kind of block. One
    block's float32 weights, their cotangents and its activations are on the
    device at a time. The table's gradient is the sum of its two uses: the
    head's product and the rows the ids name. The same numbers as ``jax.grad``
    of ``loss`` (a test holds them equal)."""
    rows = jnp.asarray(rows)
    n_rows = rows.shape[0]

    @functools.lru_cache(maxsize=None)
    def forward(kind):
        return jax.jit(lambda x, layer: block(x, layer, kind, arch))

    @functools.lru_cache(maxsize=None)
    def backward(kind):
        return jax.jit(lambda x, layer, ct: jax.vjp(
            lambda x, layer: block(x, layer, kind, arch), x, layer)[1](ct))

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
            lg = head(x, norm32, table32, arch)
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
    scatter = jax.jit(lambda d_table, ct, row: d_table.at[row].add(ct * arch["embed_scale"]))
    stack = [(where, kind) for _, kind, _, where in layers_of(params, arch)]
    d_table = jnp.zeros(params["embed"].shape, jnp.float32)
    d_norm = jnp.zeros(params["final_norm"].shape, jnp.float32)
    by_block, first_row, ce = [None] * len(stack), {}, 0.0
    add = lambda a, b: b if a is None else jax.tree.map(  # noqa: E731
        lambda x, y: (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype), a, b)
    for b in range(n_rows):
        row = rows[b]
        xs = [jax.jit(lambda t, r: embed(t, r) * arch["embed_scale"])(params["embed"], row)]
        for where, kind in stack:
            xs.append(forward(kind)(xs[-1], _leaves(params, where)))
        if b == 0:  # on the host: the backward pass needs the room
            first_row = {"logits": np.asarray(jax.jit(lambda x, n, w: head(x, n, w, arch))(
                xs[-1], params["final_norm"], params["embed"]))}
        row_ce, (ct, row_norm, row_table) = head_terms(
            xs[-1], params["final_norm"], params["embed"], row)
        ce += float(row_ce)
        d_norm, d_table = d_norm + row_norm, d_table + row_table
        del row_table
        for i in reversed(range(len(stack))):
            where, kind = stack[i]
            ct, d_layer = backward(kind)(xs[i], _leaves(params, where), ct)
            by_block[i] = add(by_block[i], d_layer)
            xs.pop()
        d_table = scatter(d_table, ct, row)
    # the blocks' gradients back under the leaves' own names, stacked over the periods
    by_name = {"['embed']": d_table.astype(params["embed"].dtype),
               "['final_norm']": d_norm.astype(params["final_norm"].dtype)}
    periods = {}
    for (where, _), d_layer in zip(stack, by_block):
        for leaf, g in d_layer.items():
            periods.setdefault(f"['layers']['{where[1]}']['{leaf}']", []).append(g)
    by_name.update({name: jnp.stack(gs) for name, gs in periods.items()})
    return ce, {**first_row, "ce": ce}, by_name
