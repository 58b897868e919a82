"""Routed expert MLP: dropless, sorted dispatch over a grouped matmul.

The layer, from the published descriptions (OLMoE, arXiv 2409.02060, and
its ``config.json``; Mixtral differs in one line), for one token's hidden
state ``h`` after the block's second RMSNorm, X experts, k chosen:

    p      = softmax_f32(h W_router)                    over all X experts
    (g, e) = top_k(p)                                   k gates, k expert ids
    g      = g / sum(g)          only if ``norm_topk``  (Mixtral: yes; OLMoE:
                                                         ``norm_topk_prob`` false)
    y      = sum_j g_j * W_down[e_j] (silu(W_gate[e_j] h) * W_up[e_j] h)

and, per layer, over the N tokens of the batch:

    load_balance = X * sum_x (rows_x / (N k)) * mean_n p[n, x]      (Switch)
    z            = mean_n logsumexp(h W_router)^2                   (ST-MoE)

Every (token, expert) pair is computed whatever the routing's skew: there
is no capacity, no dropped token and no padding that grows with the
busiest expert. The N*k pairs ("rows") are sorted by expert id (a stable
sort, so a group keeps token order), the tokens are gathered into that
order, ``ops.grouped_matmul`` multiplies each contiguous group of rows by
its own expert's matrices (group sizes are device values, a bincount of
the expert ids),
and the rows are brought back to token order and summed. The gate ``g_j``
is applied to ``silu(gate) * up`` in float32 before the down projection,
which is linear, so the result equals the equation's; the backward pass
then needs no output of the down projection.

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

from ..ops import grouped_matmul

# what a remat policy may save of the routing: a few MB a layer against a
# router product, a softmax, a top-k, two sorts and a bincount in the
# backward pass. The sorted inputs and the gate and up products are named
# too (``moe_xs``, ``moe_gate``, ``moe_up``: 537 + 2 x 268 MB a layer at
# 16k tokens); models/llama.py's ``attn`` policy leaves them out and says why.
ROUTE_NAMES = ("moe_probs", "moe_gates", "moe_order", "moe_inv", "moe_sizes")


def moe_param_axes(prefix: tuple = ()):
    """Logical axes; ``prefix`` prepends e.g. ("layers",) for stacked use."""
    return {
        "router": prefix + ("embed", "experts"),
        "w_gate": prefix + ("experts", "embed", "expert_mlp"),
        "w_up": prefix + ("experts", "embed", "expert_mlp"),
        "w_down": prefix + ("experts", "expert_mlp", "embed"),
    }


def init_moe_params(key, hidden: int, expert_mlp: int, n_experts: int, dtype,
                    n_layers: int | None = None):
    """The single source of MoE init (llama.py stacks it per layer via
    ``n_layers``)."""
    ks = jax.random.split(key, 4)
    lead = () if n_layers is None else (n_layers,)

    def init(k, shape, fan_in, out_dtype=dtype):
        return (jax.random.truncated_normal(k, -2, 2, lead + shape, jnp.float32)
                * (fan_in ** -0.5)).astype(out_dtype)

    return {
        # router stays f32: routing logits are precision-sensitive
        "router": init(ks[0], (hidden, n_experts), hidden, jnp.float32),
        "w_gate": init(ks[1], (n_experts, hidden, expert_mlp), hidden),
        "w_up": init(ks[2], (n_experts, hidden, expert_mlp), hidden),
        "w_down": init(ks[3], (n_experts, expert_mlp, hidden), expert_mlp),
    }


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


def route(tokens, router, *, top_k: int, norm_topk: bool):
    """Routing in float32: tokens [N, E] -> a dict of the gates [N, k], the
    sort ``order`` of the N*k rows by expert with its inverse, the rows per
    expert ``sizes`` [X] and the two auxiliary terms."""
    n, n_experts = tokens.shape[0], router.shape[1]
    # float32 in earnest: on a TPU a float32 product runs as one bf16 pass
    # unless asked otherwise, and a router logit off by 2^-8 reorders the
    # k-th and (k+1)-th expert of every tenth token (PERF.md, PR 26); the
    # product is 2 * N * hidden * X FLOPs, a thousandth of the experts'
    logits = jnp.einsum("nd,dx->nx", tokens.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = checkpoint_name(jax.nn.softmax(logits, axis=-1), "moe_probs")
    gates, expert_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
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
    return {
        "gates": gates,
        "order": checkpoint_name(order, "moe_order"),
        "inv": checkpoint_name(inv, "moe_inv"),
        "sizes": sizes,
        "load_balance": n_experts * jnp.sum(frac_rows * jnp.mean(probs, axis=0)),
        "z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }


def moe_block(x, params, *, top_k: int = 2, norm_topk: bool = True,
              ep_axis: str | None = None):
    """x: [B, S, E] -> ([B, S, E], aux). Routing in f32; experts in x.dtype
    with f32 accumulation.

    ``aux``: ``load_balance`` and ``z`` (scalars, this layer's auxiliary
    terms), ``rows`` [X] int32 (rows computed per expert) and ``dropped``
    (N*k less their sum: 0 by construction).

    ``ep_axis`` (inside a ``shard_map``): ``params`` hold this device's
    X/ep consecutive experts and the whole router; see the module's text.
    """
    b, s, e = x.shape
    n = b * s
    tokens = x.reshape(n, e)
    with jax.named_scope("moe_route"):
        r = route(tokens, params["router"], top_k=top_k, norm_topk=norm_topk)
    sizes, offset = r["sizes"], None
    if ep_axis is not None:
        local = params["w_gate"].shape[0]
        first = jax.lax.axis_index(ep_axis) * local
        offset = jnp.sum(jnp.where(jnp.arange(sizes.shape[0]) < first, sizes, 0))
        sizes = jax.lax.dynamic_slice_in_dim(sizes, first, local)
    gmm = functools.partial(grouped_matmul, group_sizes=sizes, row_offset=offset)
    with jax.named_scope("moe_dispatch"):
        xs = checkpoint_name(_dispatch(tokens, r["order"], r["inv"], top_k), "moe_xs")
        row_gates = _permute(r["gates"].reshape(n * top_k), r["order"], r["inv"])
    with jax.named_scope("moe_experts"):
        gate = checkpoint_name(gmm(xs, params["w_gate"]), "moe_gate")
        up = checkpoint_name(gmm(xs, params["w_up"]), "moe_up")
        act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
               * row_gates[:, None]).astype(x.dtype)
        ys = gmm(act, params["w_down"])
    with jax.named_scope("moe_combine"):
        out = _permute(ys, r["inv"], r["order"]).reshape(n, top_k, e)
        out = out.astype(jnp.float32).sum(axis=1).astype(x.dtype)
        if ep_axis is not None:
            out = jax.lax.psum(out, ep_axis)
    aux = {"load_balance": r["load_balance"], "z": r["z"], "rows": r["sizes"],
           "dropped": n * top_k - jnp.sum(r["sizes"])}
    return out.reshape(b, s, e), aux
