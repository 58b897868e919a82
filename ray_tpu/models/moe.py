"""Routed expert MLP: dropless, sorted dispatch over a grouped matmul.

The layer, from the published descriptions (OLMoE, arXiv 2409.02060, and
its ``config.json``; Mixtral differs in one line), for one token's hidden
state ``h`` after the block's second RMSNorm, X experts, k chosen, ``r`` what
the router reads (``h``; or, ``router_input``, another tensor of the token:
SmallThinker's router reads the residual stream as it ENTERS the block,
before the attention norm, so its routing depends on nothing the mixer
computes) and ``act`` the gate's activation (``silu``: SwiGLU; ``relu``:
ReGLU, SmallThinker's):

    p      = softmax_f32(r W_router)                    over all X experts
    (g, e) = top_k(p)                                   k gates, k expert ids
    g      = g / sum(g)          only if ``norm_topk``  (Mixtral: yes; OLMoE:
                                                         ``norm_topk_prob`` false)
    g      = s g                 ``routed_scale`` s     (Laguna-S-2.1: 2.5; else 1)
    y      = sum_j g_j * W_down[e_j] (act(W_gate[e_j] h) * W_up[e_j] h)

and, per layer, over the N tokens of the batch:

    load_balance = X * sum_x (rows_x / (N k)) * mean_n p[n, x]      (Switch)
    z            = mean_n logsumexp(r W_router)^2                   (ST-MoE)

Every (token, expert) pair is computed whatever the routing's skew: there
is no capacity, no dropped token and no padding that grows with the
busiest expert. The N*k pairs ("rows") are sorted by expert id (a stable
sort, so a group keeps token order), the tokens are gathered into that
order, ``ops.grouped_matmul`` multiplies each contiguous group of rows by
its own expert's matrices (group sizes are device values, a bincount of
the expert ids),
and the rows are brought back to token order and summed. The gate ``g_j``
is applied to ``act(gate) * up`` (``gate_act``, the one place the activation
is written) in float32 before the down projection,
which is linear, so the result equals the equation's; the backward pass
then needs no output of the down projection. Under ``relu`` the layer counts
``act_zero``: the share of the computed rows' gate products that the
activation sets to zero (rows of padding left out), which is what a down
product that skipped those columns would be sized by.

A shared expert (Qwen3-Next, DeepSeek: leaves ``w_shared_*`` present) is a
gated unit of the same activation every token passes through, scaled by
``sigmoid(h w_shared_scale)``
where that leaf is present (Qwen's; DeepSeek's has none and is plain), and
added to the routed sum; it has its own scope, ``moe_shared``.

A sigmoid router (DeepSeek-V3, arXiv 2412.19437; ``score="sigmoid"``):

    sc     = sigmoid_f32(h W_router)                    X independent scores
    e      = top_k(sc + b)                              b: ``router_bias``
    g      = sc[e] / sum(sc[e])  if ``norm_topk``

``b`` enters the choice alone, so no gradient reaches it; after a step it
moves by ``bias_step``: ``b += rate sign(mean rows - rows_x)``, from that
step's rows per expert. The balance term is taken a SEQUENCE at a time:
``mean over sequences of sum_x (X rows_x(seq) / (k S)) mean_t sc'[t, x]``,
``sc' = sc / sum_x sc``; there is no z term.

One chip's share of the experts on the plain ``jit`` path (``held`` =
(first, count), static): the router keeps its published width and routes
over all X experts, the expert leaves hold ``count`` consecutive experts
from ``first``, and the layer returns what THOSE experts add (plus the
shared expert, which every chip computes alike). Rows of absent experts
cost no grouped-matmul tile and add nothing; nothing is exchanged and
nothing stands in for the exchange. The held rows are a contiguous range
of the sorted rows, and the dispatch gathers that range alone: ``cap`` rows
from its first, ``cap`` a static ``HELD_CAPACITY`` times the range's even
share, the results added into their tokens by ``ops/moe_rows.py``'s
``sum_rows`` (the kernel ``moe_rows``: a token's rows fetched by id and
summed in float32 in VMEM, where XLA's scatter-add went one row at a time
through a float32 ``[N, E]``; the gather is XLA's, and its gradient is the
same kernel). No row is dropped whatever the skew: a step whose range is
longer than ``cap`` takes, under a ``lax.cond``, the path that walks the
range expert by expert, a chunk of rows at a time, for as many turns as it
is long (``_held_by_expert``).

Expert parallelism inside a ``shard_map`` (``ep_axis``): routing is
global (the router is replicated), the sort is the same on every device,
each device holds X/ep consecutive experts and therefore one contiguous
range of the sorted rows, computes that range (rows outside it come back
zero), and a ``psum`` over ``ep`` adds the partial outputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.tracing import device_scope
from ..ops import grouped_matmul
from ..ops.moe_rows import add_rows, take_rows
from .kinds import LayerKind

# what a remat policy may save of the routing: a few MB a layer against a
# router product, a softmax, a top-k, two sorts and a bincount in the
# backward pass. The sorted inputs and the gate and up products are named
# too (``moe_xs``, ``moe_gate``, ``moe_up``: 537 + 2 x 268 MB a layer at
# 16k tokens); models/llama.py's ``attn`` policy leaves them out and says why.
ROUTE_NAMES = ("moe_probs", "moe_gates", "moe_order", "moe_inv", "moe_sizes")


def moe_param_axes(prefix: tuple = (), shared: bool = False, *, shared_gate: bool = True,
                   bias: bool = False):
    """Logical axes; ``prefix`` prepends e.g. ("layers",) for stacked use."""
    axes = {
        "router": prefix + ("embed", "experts"),
        "w_gate": prefix + ("experts", "embed", "expert_mlp"),
        "w_up": prefix + ("experts", "embed", "expert_mlp"),
        "w_down": prefix + ("experts", "expert_mlp", "embed"),
    }
    if shared:
        axes.update({
            "w_shared_scale": prefix + ("embed",),
            "w_shared_gate": prefix + ("embed", "mlp"),
            "w_shared_up": prefix + ("embed", "mlp"),
            "w_shared_down": prefix + ("mlp", "embed"),
        })
        if not shared_gate:
            del axes["w_shared_scale"]
    if bias:
        axes["router_bias"] = prefix + ("experts",)
    return axes


def init_moe_params(key, hidden: int, expert_mlp: int, n_experts: int, dtype,
                    n_layers: int | None = None, *, held: int | None = None,
                    shared_mlp: int = 0, shared_gate: bool = True, bias: bool = False):
    """The single source of MoE init (llama.py stacks it per layer via
    ``n_layers``). ``held``: how many of the ``n_experts`` the router scores
    have leaves here (all of them when None); ``shared_mlp``: the width of
    the shared expert, 0 for none, ``shared_gate`` whether it has a sigmoid
    scale; ``bias``: the router's selection bias, float32, zero."""
    ks = jax.random.split(key, 4)
    lead = () if n_layers is None else (n_layers,)
    held = n_experts if held is None else held

    def init(k, shape, fan_in, out_dtype=dtype):
        return (jax.random.truncated_normal(k, -2, 2, lead + shape, jnp.float32)
                * (fan_in ** -0.5)).astype(out_dtype)

    params = {
        # router stays f32: routing logits are precision-sensitive
        "router": init(ks[0], (hidden, n_experts), hidden, jnp.float32),
        "w_gate": init(ks[1], (held, hidden, expert_mlp), hidden),
        "w_up": init(ks[2], (held, hidden, expert_mlp), hidden),
        "w_down": init(ks[3], (held, expert_mlp, hidden), expert_mlp),
    }
    if shared_mlp:
        ss = jax.random.split(jax.random.fold_in(key, 1), 4)
        params.update({
            "w_shared_scale": init(ss[0], (hidden,), hidden),
            "w_shared_gate": init(ss[1], (hidden, shared_mlp), hidden),
            "w_shared_up": init(ss[2], (hidden, shared_mlp), hidden),
            "w_shared_down": init(ss[3], (shared_mlp, hidden), shared_mlp),
        })
        if not shared_gate:
            del params["w_shared_scale"]
    if bias:
        params["router_bias"] = jnp.zeros(lead + (n_experts,), jnp.float32)
    return params


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(tokens, order, inv, top_k):
    """tokens [N, E] -> rows [N*k, E] in sorted order: row r is token
    ``order[r] // k``. The gradient is a gather too (by the inverse
    permutation, then a sum over a token's k rows), not a scatter-add."""
    return tokens[order // top_k]


def _dispatch_fwd(tokens, order, inv, top_k):
    return _dispatch(tokens, order, inv, top_k), (order, inv)


def _dispatch_bwd(top_k, res, g):
    _, inv = res
    d = g[inv].reshape(-1, top_k, g.shape[-1])
    return d.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(values, perm, inv_perm):
    """``values[perm]`` along the leading axis, for a permutation and its
    inverse: the gradient is the inverse gather, where the transpose of a
    plain gather would be a scatter-add."""
    return values[perm]


_permute.defvjp(lambda values, perm, inv_perm: (values[perm], (inv_perm,)),
                lambda res, g: (g[res[0]], None, None))


def route(tokens, router, *, top_k: int, norm_topk: bool, score: str = "softmax",
          bias=None, n_seqs: int = 1, scale: float = 1.0):
    """Routing in float32: tokens [N, E] -> a dict of the gates [N, k], the
    sort ``order`` of the N*k rows by expert with its inverse, the rows per
    expert ``sizes`` [X] and the two auxiliary terms. ``score`` "sigmoid"
    with the selection ``bias`` [X] and the tokens' ``n_seqs`` sequences:
    the module's text. ``scale`` multiplies the gates, after ``norm_topk``."""
    n, n_experts = tokens.shape[0], router.shape[1]
    # float32 in earnest: on a TPU a float32 product runs as one bf16 pass
    # unless asked otherwise, and a router logit off by 2^-8 reorders the
    # k-th and (k+1)-th expert of every tenth token (PERF.md, PR 26); the
    # product is 2 * N * hidden * X FLOPs, a thousandth of the experts'
    logits = jnp.einsum("nd,dx->nx", tokens.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        probs = checkpoint_name(jax.nn.sigmoid(logits), "moe_probs")
        biased = probs if bias is None else probs + jax.lax.stop_gradient(bias)
        expert_idx = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)[1]
        gates = jnp.take_along_axis(probs, expert_idx, axis=-1)
    else:
        probs = checkpoint_name(jax.nn.softmax(logits, axis=-1), "moe_probs")
        gates, expert_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gates = gates * scale
    gates = checkpoint_name(gates, "moe_gates")
    flat = expert_idx.reshape(n * top_k)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    # a bincount as a compare-and-sum: it fuses, where a scatter-add of the
    # N*k ids into X bins takes 1.3 ms at 131,072 rows on a v5e (PR 26)
    sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :], axis=0,
                    dtype=jnp.int32)
    sizes = checkpoint_name(sizes, "moe_sizes")
    frac_rows = jax.lax.stop_gradient(sizes.astype(jnp.float32)) / (n * top_k)
    if score == "sigmoid":
        per_seq = n // n_seqs
        chosen = jnp.sum(jax.nn.one_hot(expert_idx.reshape(n_seqs, per_seq * top_k),
                                        n_experts, dtype=jnp.float32), axis=1)
        share = (probs / jnp.sum(probs, axis=-1, keepdims=True)).reshape(
            n_seqs, per_seq, n_experts)
        balance = jnp.mean(jnp.sum(
            n_experts * chosen / (per_seq * top_k) * jnp.mean(share, axis=1), axis=-1))
        z = jnp.zeros((), jnp.float32)
    else:
        balance = n_experts * jnp.sum(frac_rows * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return {
        "gates": gates,
        "order": checkpoint_name(order, "moe_order"),
        "inv": checkpoint_name(inv, "moe_inv"),
        "sizes": sizes,
        "load_balance": balance,
        "z": z,
    }


def bias_step(bias, rows, rate: float):
    """The selection bias after a step: up by ``rate`` for an expert that got
    fewer rows than the mean, down for one that got more. ``bias`` and
    ``rows`` [..., X]."""
    rows = rows.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(rows, axis=-1, keepdims=True) - rows)


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def gate_act(gate, activation: str = "silu"):
    """``act(gate)`` in float32, the gate of a gated unit ``act(gate) * up``
    (``silu``: SwiGLU; ``relu``: ReGLU): the one place that says which."""
    return ACTIVATIONS[activation](gate.astype(jnp.float32))


def _zeroed(gate, counted, activation: str) -> dict:
    """A dispatch path's counts: ``{"zeroed": how many elements of the gate
    product [M, F] the activation sets to zero}`` over the rows ``counted``
    [M] (None: all), float32; ``{}`` for an activation that zeroes none (an
    empty tree adds nothing to such a layer's program)."""
    if activation != "relu":
        return {}
    cut = gate <= 0
    if counted is not None:
        cut &= counted[:, None]
    return {"zeroed": jnp.sum(cut, dtype=jnp.float32)}


def shared_expert(tokens, params, activation: str = "silu"):
    """The always-on expert on tokens [N, E]: a gated unit scaled, token by
    token, by ``sigmoid(h . w_shared_scale)`` where it has that leaf."""
    if "w_shared_scale" not in params:
        gate = jnp.einsum("ne,em->nm", tokens, params["w_shared_gate"])
        up = jnp.einsum("ne,em->nm", tokens, params["w_shared_up"])
        act = (gate_act(gate, activation) * up.astype(jnp.float32)).astype(tokens.dtype)
        return jnp.einsum("nm,me->ne", act, params["w_shared_down"])
    scale = jax.nn.sigmoid(jnp.einsum(
        "ne,e->n", tokens, params["w_shared_scale"], preferred_element_type=jnp.float32))
    gate = jnp.einsum("ne,em->nm", tokens, params["w_shared_gate"])
    up = jnp.einsum("ne,em->nm", tokens, params["w_shared_up"])
    act = (gate_act(gate, activation) * up.astype(jnp.float32)
           * scale[:, None]).astype(tokens.dtype)
    return jnp.einsum("nm,me->ne", act, params["w_shared_down"])


# rows the compact path of a held range is compiled for, as a multiple of
# the range's even share N k count / X: with seeded weights and uniform ids a
# range's share stays within a few percent of even
HELD_CAPACITY = 2.0


def _held_capacity(n_rows: int, held, n_experts: int) -> int | None:
    """Static row count of the compact path for ``held`` = (first, count) of
    ``n_experts``, or None where it would save nothing (no held range, or a
    bound of at least half of all rows)."""
    if held is None:
        return None
    cap = int(HELD_CAPACITY * n_rows * held[1] / n_experts)
    cap = -(-cap // 512) * 512 if cap >= 512 else -(-cap // 16) * 16
    return cap if 2 * cap <= n_rows else None


def _experts(xs, row_gates, weights, gmm, activation, counted=None):
    """The three grouped products on sorted rows xs [M, E], the gate applied
    to the activation in float32: ([M, E], ``_zeroed``'s count over the rows
    ``counted``)."""
    gate = checkpoint_name(gmm(xs, weights["w_gate"]), "moe_gate")
    up = checkpoint_name(gmm(xs, weights["w_up"]), "moe_up")
    act = (gate_act(gate, activation) * up.astype(jnp.float32)
           * row_gates[:, None]).astype(xs.dtype)
    return gmm(act, weights["w_down"]), _zeroed(gate, counted, activation)


def _all_rows_counted(top_k, tokens, weights, gates, order, inv, sizes, offset,
                      activation="silu"):
    """Every one of the N*k sorted rows is gathered; the grouped matmul
    computes the groups' range of them (all, when ``offset`` is None):
    ([N, E], ``_zeroed``'s counts)."""
    n, e = tokens.shape
    counted = None
    if offset is not None and activation == "relu":  # the rows the groups hold
        rows = jnp.arange(n * top_k, dtype=jnp.int32)
        counted = (rows >= offset) & (rows < offset + jnp.sum(sizes))
    gmm = functools.partial(grouped_matmul, group_sizes=sizes, row_offset=offset)
    with device_scope("moe_dispatch"):
        xs = checkpoint_name(_dispatch(tokens, order, inv, top_k), "moe_xs")
        row_gates = _permute(gates.reshape(n * top_k), order, inv)
    with device_scope("moe_experts"):
        ys, counts = _experts(xs, row_gates, weights, gmm, activation, counted)
    with device_scope("moe_combine"):
        out = _permute(ys, inv, order).reshape(n, top_k, e)
        return out.astype(jnp.float32).sum(axis=1).astype(tokens.dtype), counts


def _held_rows(top_k, cap, tokens, weights, gates, order, sizes, offset, activation="silu"):
    """Only the held experts' rows: the ``cap`` sorted rows from the held
    range's first. ``take_rows`` (XLA's gather) brings their tokens in,
    ``add_rows`` (the kernel ``moe_rows``) adds each token's rows back in
    float32, and each is the other's gradient. Rows past the range's end
    name no token: they belong to no group, come back zero and are added
    nowhere."""
    n = tokens.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    valid = rows < jnp.sum(sizes)
    pair = order[jnp.minimum(offset + rows, n * top_k - 1)]  # token * k + choice
    token = jnp.where(valid, pair // top_k, -1)
    gmm = functools.partial(grouped_matmul, group_sizes=sizes,
                            row_offset=jnp.zeros((), jnp.int32))
    with device_scope("moe_dispatch"):
        xs = checkpoint_name(take_rows(tokens, token), "moe_xs")
        row_gates = jnp.where(valid, gates.reshape(n * top_k)[pair], 0.0)
    with device_scope("moe_experts"):
        ys, counts = _experts(xs, row_gates, weights, gmm, activation, valid)
    with device_scope("moe_combine"):
        return add_rows(ys, token, n), counts


def _glu_rows(xs, weights, row_gates, activation, counted=None):
    """ONE expert (``weights``: its three matrices) on rows xs [M, E], the
    gate applied to the activation in float32, as ``_experts``: ([M, E], its
    count)."""
    gate = jnp.dot(xs, weights["w_gate"])
    act = (gate_act(gate, activation) * jnp.dot(xs, weights["w_up"]).astype(jnp.float32)
           * row_gates[:, None]).astype(xs.dtype)
    return jnp.dot(act, weights["w_down"]), _zeroed(gate, counted, activation)


def _held_by_expert(top_k, cap, tokens, weights, gates, order, sizes, offset, g=None,
                    activation="silu"):
    """The held range when it is longer than ``cap``: expert by expert, an
    expert's share of ``cap`` sorted rows at a time, each chunk a plain gated unit
    on its gathered tokens added into them, for as many chunks as the expert
    has rows (loops whose counts are device values). Nothing here is as long as the N*k rows, which
    ``_all_rows_counted`` gathers whole (0.94 GB a [N*k, E] tensor at 8,192 tokens x 8
    of width 7,168: as this branch of the ``cond``, which an even router never
    takes, it cost a step 4.5 GB of scratch by the chip compiler's count).
    It returns (result, ``_zeroed``'s count); with a cotangent ``g`` [N, E]
    the gradients of (tokens, weights, gates) instead: a loop of unknown
    length has no
    reverse pass of its own, so each chunk's is taken where it is made again."""
    n = tokens.shape[0]
    flat_gates = gates.reshape(n * top_k)
    ends = jnp.cumsum(sizes)
    f32 = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)  # noqa: E731
    # rows a chunk: an expert's even share of ``cap`` (in sublane tiles), so
    # that a range of 2-3 x ``cap`` costs 2-3 chunks an expert and not
    # ``cap`` rows for each of them
    chunk_rows = -(-cap // (sizes.shape[0] * 16)) * 16

    def expert(e, total):
        w = jax.tree.map(lambda a: a[e], weights)
        n_chunks = (sizes[e] + chunk_rows - 1) // chunk_rows

        def chunk(j):
            """The j-th chunk of this expert's sorted rows: (token * k + choice,
            which of them are its rows, their tokens, their gates)."""
            rows = j * chunk_rows + jnp.arange(chunk_rows, dtype=jnp.int32)
            valid = rows < sizes[e]
            pair = order[jnp.minimum(offset + ends[e] - sizes[e] + rows, n * top_k - 1)]
            return pair, valid, tokens[pair // top_k], jnp.where(valid, flat_gates[pair], 0.0)

        def forward(j, carry):
            out, counts = carry
            pair, valid, rows, row_gates = chunk(j)
            keep = valid[:, None]
            ys, zeroed = _glu_rows(rows, w, row_gates, activation, valid)
            ys = jnp.where(keep, ys, 0)
            return (out.at[pair // top_k].add(ys.astype(jnp.float32)),
                    jax.tree.map(jnp.add, counts, zeroed))

        def backward(j, carry):
            d_tokens, d_w, d_gates = carry
            pair, valid, rows, row_gates = chunk(j)
            pull = jax.vjp(lambda *a: _glu_rows(*a, activation)[0], rows, w, row_gates)[1]
            d_rows, d, d_row_gates = pull(jnp.where(valid[:, None], g[pair // top_k], 0))
            return (d_tokens.at[pair // top_k].add(d_rows.astype(jnp.float32)),
                    jax.tree.map(lambda a, x: a + x.astype(jnp.float32), d_w, d),
                    d_gates.at[pair].add(jnp.where(valid, d_row_gates, 0.0)))

        if g is None:
            return jax.lax.fori_loop(0, n_chunks, forward, total)
        d_tokens, d_weights, d_gates = total
        d_tokens, d_w, d_gates = jax.lax.fori_loop(0, n_chunks, backward,
                                                   (d_tokens, f32(w), d_gates))
        # an expert's slice written where it lies
        return (d_tokens, jax.tree.map(lambda a, d: jax.lax.dynamic_update_index_in_dim(
            a, d.astype(a.dtype), e, 0), d_weights, d_w), d_gates)

    with device_scope("moe_experts"):
        count = sizes.shape[0]
        if g is None:
            # the counts start as those of no row: 0, or nothing to count
            out, counts = jax.lax.fori_loop(
                0, count, expert,
                (f32(tokens), _zeroed(jnp.zeros((0, 1)), None, activation)))
            return out.astype(tokens.dtype), counts
        d_tokens, d_weights, d_gates = jax.lax.fori_loop(0, count, expert, (
            f32(tokens), jax.tree.map(jnp.zeros_like, weights), f32(flat_gates)))
        return d_tokens.astype(tokens.dtype), d_weights, d_gates.reshape(gates.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 8))
def _held_range_counted(top_k, cap, tokens, weights, gates, order, sizes, offset,
                        activation="silu"):
    """The held range by the compact path, or, in a step whose range is
    longer than ``cap``, expert by expert in as many turns as it takes:
    dropless whatever the skew. One ``lax.cond`` forward and one backward,
    each running the branch taken: differentiating a plain ``cond`` keeps
    BOTH branches' residuals, so the backward rule runs its branch again
    instead. Returns ([N, E], ``_zeroed``'s counts); the counts take no
    gradient."""
    args = (top_k, cap, tokens, weights, gates, order, sizes, offset)
    return jax.lax.cond(
        jnp.sum(sizes) <= cap,
        lambda: _held_rows(*args, activation=activation),
        lambda: _held_by_expert(*args, activation=activation))


def _held_range_fwd(top_k, cap, *args):
    return _held_range_counted(top_k, cap, *args), args[:6]


def _held_range_bwd(top_k, cap, activation, args, g):
    tokens, weights, gates, order, sizes, offset = args
    g, _ = g
    d = jax.lax.cond(
        jnp.sum(sizes) <= cap,
        lambda: jax.vjp(lambda t, w, gt: _held_rows(top_k, cap, t, w, gt, order, sizes, offset,
                                                    activation)[0],
                        tokens, weights, gates)[1](g),
        lambda: _held_by_expert(top_k, cap, tokens, weights, gates, order, sizes, offset, g,
                                activation))
    return (*d, None, None, None)


_held_range_counted.defvjp(_held_range_fwd, _held_range_bwd)


def route_block(tokens, params, n_seqs: int, *, top_k: int = 2, norm_topk: bool = True,
                score: str = "softmax", routed_scale: float = 1.0):
    """``route`` of tokens [N, E] (``n_seqs`` sequences) by ``params``' router,
    under the scope ``moe_route``: what ``moe_block`` does first, callable
    apart from it where the router reads another tensor than the experts."""
    with device_scope("moe_route"):
        return route(tokens, params["router"], top_k=top_k, norm_topk=norm_topk, score=score,
                     bias=params.get("router_bias"), n_seqs=n_seqs, scale=routed_scale)


def moe_block(x, params, *, top_k: int = 2, norm_topk: bool = True,
              ep_axis: str | None = None, held: tuple[int, int] | None = None,
              score: str = "softmax", routed_scale: float = 1.0,
              activation: str = "silu", routed: dict | None = None):
    """x: [B, S, E] -> ([B, S, E], aux). Routing in f32; experts in x.dtype
    with f32 accumulation. ``routed``: the routing, where it was made from
    another tensor of the same tokens than ``x`` (``route_block`` with the
    same keywords; None: made here, from ``x``). ``activation``: the
    experts' gate, "silu" | "relu".

    ``aux``: ``load_balance`` and ``z`` (scalars, this layer's auxiliary
    terms), ``rows`` [X] int32 (rows routed to each expert the router
    scores), ``dropped`` (N*k less their sum: 0 by construction),
    with ``held``, ``rows_held`` [count] int32 (rows computed by each
    expert held here) and ``held_share`` (their sum over N*k), and under
    "relu" ``act_zero`` (the module's text).

    ``ep_axis`` (inside a ``shard_map``): ``params`` hold this device's
    X/ep consecutive experts and the whole router. ``held`` = (first,
    count), static, on the plain path: ``params`` hold those experts and
    the whole router, and the result is their part of the routed sum. See
    the module's text for both. ``routed_scale``: the routed experts' sum
    times that much (on their gates), the shared expert plain.
    """
    b, s, e = x.shape
    n = b * s
    tokens = x.reshape(n, e)
    r = routed if routed is not None else route_block(
        tokens, params, b, top_k=top_k, norm_topk=norm_topk, score=score,
        routed_scale=routed_scale)
    sizes, offset = r["sizes"], None
    if ep_axis is not None or held is not None:
        local = params["w_gate"].shape[0]
        if held is not None:
            first, count = held
            if count != local:
                raise ValueError(f"held says {count} experts, the leaves hold {local}")
        else:
            first = jax.lax.axis_index(ep_axis) * local
        offset = jnp.sum(jnp.where(jnp.arange(sizes.shape[0]) < first, sizes, 0))
        sizes = jax.lax.dynamic_slice_in_dim(sizes, first, local)
    weights = {k: params[k] for k in ("w_gate", "w_up", "w_down")}
    cap = _held_capacity(n * top_k, held, r["sizes"].shape[0])
    if cap is None:
        out, counts = _all_rows_counted(top_k, tokens, weights, r["gates"], r["order"],
                                        r["inv"], sizes, offset, activation)
    else:
        out, counts = _held_range_counted(top_k, cap, tokens, weights, r["gates"], r["order"],
                                          sizes, offset, activation)
    if ep_axis is not None:
        with device_scope("moe_combine"):
            out = jax.lax.psum(out, ep_axis)
    if "w_shared_gate" in params:
        with device_scope("moe_shared"):
            out = out + shared_expert(tokens, params, activation)
    aux = {"load_balance": r["load_balance"], "z": r["z"], "rows": r["sizes"],
           "dropped": n * top_k - jnp.sum(r["sizes"])}
    if held is not None:
        aux["rows_held"] = sizes
        aux["held_share"] = jnp.sum(sizes).astype(jnp.float32) / (n * top_k)
    if counts:
        computed = jnp.sum(sizes).astype(jnp.float32) * params["w_gate"].shape[-1]
        aux["act_zero"] = jax.lax.stop_gradient(counts["zeroed"]) / jnp.maximum(computed, 1.0)
    return out.reshape(b, s, e), aux


def _moe_axes(c) -> dict:
    return moe_param_axes(shared=c.moe_shared > 0, shared_gate=c.moe_shared_gate,
                          bias=c.moe_bias_rate > 0)


def _moe_init(c, keys, lead, normal) -> dict:
    return init_moe_params(
        keys[0], hidden=c.hidden, expert_mlp=c.intermediate, n_experts=c.moe_experts,
        dtype=c.dtype, n_layers=lead[0] if lead else None,
        held=c.moe_held[1] if c.moe_held else None, shared_mlp=c.moe_shared,
        shared_gate=c.moe_shared_gate, bias=c.moe_bias_rate > 0)


def _routing(c) -> dict:
    return dict(top_k=c.moe_top_k, norm_topk=c.moe_norm_topk, score=c.moe_score,
                routed_scale=c.moe_routed_scale)


def _moe_early(x, layer, *, config):
    """The routing, where the router reads the block's input ``x``."""
    if config.moe_router_input != "block":
        return None
    return route_block(x.reshape(-1, x.shape[-1]), layer, x.shape[0], **_routing(config))


def _moe_apply(h, layer, *, config, mesh=None, ep_axis=None, early=None):
    c = config
    return moe_block(h, layer, ep_axis=ep_axis, held=c.moe_held, activation=c.moe_activation,
                     routed=early, **_routing(c))


def _moe_matmul_params(c) -> float:
    """What a token passes through: the router, the shared expert, and its
    ``top_k`` experts' three matrices; with a held range, the held experts'
    share of them in expectation (``top_k x count / X``)."""
    share = c.moe_held[1] / c.moe_experts if c.moe_held else 1.0
    return (c.hidden * c.moe_experts + 3 * c.hidden * c.moe_shared
            + (c.hidden if c.moe_shared and c.moe_shared_gate else 0)
            + c.moe_top_k * share * 3 * c.hidden * c.intermediate)


MOE = LayerKind(axes=_moe_axes, init=_moe_init, apply=_moe_apply, early=_moe_early,
                matmul_params=_moe_matmul_params, save_names=ROUTE_NAMES)
