"""The yardstick's arithmetic for the decoder whose router reads the block's
input ahead of attention (SmallThinker-21BA3B: un-roped full layers beside
roped layers under a 4,096-key window, 28 query heads over 4 kv heads in
both, no leading layer, no shared expert, a chip's share of 64 softmax-routed
ReGLU experts and of the vocabulary), kept with the benchmark so that it does
not move with the program (``tests/benchmark_suite/test_bm_prerouted.py``
holds it equal to ``ray_tpu.models.llama.train_flops_per_token`` and to what
the kernels record of themselves, ``kernel_costs()``).

USEFUL work only: a window layer's attention counts the band's keys (at most
``sliding_window_size`` a query), a full layer's the causal triangle's; the
expert layer counts the rows the held experts compute in expectation, every
column of them (what ReLU zeroes is still multiplied: ``moe.act_zero_share``
says how much of the down product that is).
"""

from __future__ import annotations

from .flops_swa import kept_pairs, roofline_seconds, window_share

__all__ = ["kinds", "layer_kinds", "period", "kept_pairs", "roofline_seconds", "window_share",
           "expert_layer_forward_flops", "forward_flops_by_part", "train_flops_per_token",
           "attention_kernel_costs", "param_count"]

# (sliding_window_layout, rope_layout) of a layer -> its mixer kind
KIND_OF = {(0, 0): "gqa", (1, 1): "gqa_win"}


def layer_kinds(model: dict) -> list[str]:
    """The mixer kind of every layer run, from the two layouts."""
    pairs = list(zip(model["sliding_window_layout"], model["rope_layout"]))
    assert len(pairs) == model["num_hidden_layers"], "a layout entry a layer"
    return [KIND_OF[p] for p in pairs]


def kinds(model: dict) -> dict:
    """The two mixer kinds' widths, by the names the program and the reference
    use (``GroupedQueryAttention``'s fields): the full layers have no rope
    (``rope_theta`` 0), the window layers plain rope on every feature of a
    head."""
    assert model["rope_scaling"] is None
    common = dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
                  head_dim=model["head_dim"])
    return {"gqa": dict(common, rope_theta=0.0, window=0),
            "gqa_win": dict(common, rope_theta=float(model["rope_theta"]),
                            window=model["sliding_window_size"])}


def period(model: dict) -> list[str]:
    """The mixer kinds of one period of the stack: the shortest unit that,
    repeated, gives the layers."""
    names = layer_kinds(model)
    for n in range(1, len(names) + 1):
        if len(names) % n == 0 and names == names[:n] * (len(names) // n):
            return names[:n]
    raise ValueError("no layer")


def _mixer_params(a: dict, hidden: int) -> int:
    return hidden * a["head_dim"] * (2 * a["heads"] + 2 * a["kv_heads"])


def _scores_flops(a: dict, seq: int) -> float:
    """Scores and values of one mixer, forward FLOPs a token, kept keys only."""
    return 2.0 * a["heads"] * 2 * a["head_dim"] * kept_pairs(seq, a["window"] or seq) / seq


def expert_layer_forward_flops(model: dict) -> float:
    """Forward FLOPs a token of ONE expert layer on this chip: the router at
    its published width and the routed experts AT THE ROWS HELD IN EXPECTATION
    (``moe_num_active_primary_experts`` x held / published: 6 x 16 / 64 = 1.5
    experts a token; the program reports what it was)."""
    hidden, width = model["hidden_size"], model["moe_ffn_hidden_size"]
    held = model["moe_num_primary_experts"] / model["router_width"]
    return 2.0 * (hidden * model["router_width"]
                  + model["moe_num_active_primary_experts"] * held * 3 * hidden * width)


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, names, spec = model["hidden_size"], layer_kinds(model), kinds(model)
    router = 2.0 * hidden * model["router_width"]
    return {
        "full_scores": sum(_scores_flops(spec[n], seq) for n in names if n == "gqa"),
        "window_scores": sum(_scores_flops(spec[n], seq) for n in names if n == "gqa_win"),
        "projections": sum(2.0 * _mixer_params(spec[n], hidden) for n in names),
        "router": len(names) * router,
        "experts": len(names) * (expert_layer_forward_flops(model) - router),
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather is excluded; recomputed operations and the keys a kernel walks and
    does not keep do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


_FLASH_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkdv": 4}


def attention_kernel_costs(model: dict, kind: str, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each attention kernel of a layer of
    ``kind`` (``gqa``: ``flash_*``; ``gqa_win``: ``attn_win_*``) at ``batch``
    rows of ``seq``: {kernel: (flops, bytes)}, counted as
    ``flops_swa.attention_kernel_costs`` counts them: a product over a head's D
    features costs 2 D a (query, key) pair, two forward, three in dQ, four in
    dK/dV; the band's pairs under a window, half the square for the plain
    kernels (as they count themselves); bytes are the operands and results
    once in bf16, q-shaped arrays at the 28 QUERY heads, k and v at the 4 kv
    heads, the float32 statistics, and dK and dV, which leave the kernel at
    the query heads' count and are summed by sevens outside."""
    a = kinds(model)[kind]
    h, kv, d = a["heads"], a["kv_heads"], a["head_dim"]
    pairs = kept_pairs(seq, a["window"]) if a["window"] else seq * seq / 2
    q_b, kv_b, stats = batch * h * seq * d * 2, 2 * batch * kv * seq * d * 2, batch * h * seq * 4
    nbytes = {"fwd": 2 * q_b + kv_b + 128 * stats,
              "bwd_dq": 3 * q_b + kv_b + 2 * stats,
              "bwd_dkdv": 2 * q_b + kv_b + 2 * stats + 2 * q_b}
    name = "attn_win_" if a["window"] else "flash_"
    return {name + part: (n * 2.0 * batch * h * pairs * d, nbytes[part])
            for part, n in _FLASH_PRODUCTS.items()}


def param_count(model: dict) -> int:
    """Every parameter held here: a layer's mixer, its two norms, the router
    at its published width and the held experts' three matrices; both
    embeddings over the vocabulary's slice and the final norm."""
    hidden, spec = model["hidden_size"], kinds(model)
    layer = (2 * hidden + model["router_width"] * hidden
             + model["moe_num_primary_experts"] * 3 * hidden * model["moe_ffn_hidden_size"])
    return (sum(_mixer_params(spec[n], hidden) + layer for n in layer_kinds(model))
            + 2 * hidden * model["vocab_size"] + hidden)
