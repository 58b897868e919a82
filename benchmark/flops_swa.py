"""The yardstick's arithmetic for the decoder of full and window layers of
grouped-query attention with a head count a kind (Laguna-S-2.1: YaRN in the
full layers, a 512-key window in the others, a head-wise gate, a leading
dense layer, a chip's share of softmax-routed experts under a routed scale
and of the vocabulary), kept with the benchmark so that it does not move with
the program (``tests/benchmark_suite/test_bm_swa.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` and to what the kernels record
of themselves, ``kernel_costs()``).

USEFUL work only: a window layer's attention counts the band's keys (at most
``sliding_window`` a query), a full layer's the causal triangle's; the window
kernels walk two 512-blocks a query block, so they read at most about half
their roofline, and none can read over 100.
"""

from __future__ import annotations

from .flops_sparse import kept_pairs, roofline_seconds

__all__ = ["kinds", "layer_kinds", "lead_layers", "period", "kept_pairs", "roofline_seconds",
           "window_share", "expert_layer_forward_flops", "forward_flops_by_part",
           "train_flops_per_token", "attention_kernel_costs", "param_count"]

KIND_OF = {"full_attention": "gqa", "sliding_attention": "gqa_win"}


def kinds(model: dict) -> dict:
    """The two mixer kinds' widths, by the names the program and the
    reference use (``GroupedQueryAttention``'s fields; ``yarn`` a dict of
    ``Yarn``'s). A kind's head count is that of its layers in
    ``num_attention_heads_per_layer``."""
    heads = {}
    for kind, n in zip(layer_kinds(model), model["num_attention_heads_per_layer"]):
        assert heads.setdefault(kind, n) == n, "one head count a kind of layer"
    gate = {"per-head": "headwise"}[model["gating"]]
    assert set(model["gating_types"]) == {"per_head"}
    out = {}
    for layer_type, kind in KIND_OF.items():
        rope = model["rope_parameters"][layer_type]
        yarn = None
        if rope["rope_type"] == "yarn":
            yarn = dict(factor=float(rope["factor"]),
                        original_length=int(rope["original_max_position_embeddings"]),
                        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
                        attention_factor=float(rope["attention_factor"]))
        else:
            assert rope["rope_type"] == "default"
        rotary = int(model["head_dim"] * rope["partial_rotary_factor"])
        out[kind] = dict(
            heads=heads[kind], kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], rope_theta=float(rope["rope_theta"]),
            rotary_dim=0 if rotary == model["head_dim"] else rotary, yarn=yarn,
            window=model["sliding_window"] if kind == "gqa_win" else 0, gate=gate)
    return out


def layer_kinds(model: dict) -> list[str]:
    return [KIND_OF[t] for t in model["layer_types"]]


def lead_layers(model: dict) -> int:
    """The leading layers whose MLP is dense (``mlp_layer_types``)."""
    types = model["mlp_layer_types"]
    lead = types.index("sparse")
    assert set(types[:lead]) <= {"dense"} and set(types[lead:]) == {"sparse"}
    assert model["mlp_only_layers"] == list(range(lead))
    return lead


def period(model: dict) -> list[str]:
    """The mixer kinds of one period of the stack: the shortest unit that,
    repeated, gives the layers after the leading ones."""
    rest = layer_kinds(model)[lead_layers(model):]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and rest == rest[:n] * (len(rest) // n):
            return rest[:n]
    raise ValueError("no layer after the leading ones")


def window_share(seq: int, window: int) -> float:
    """(query, key) pairs a window layer attends over the causal pairs."""
    return kept_pairs(seq, window) / (seq * (seq + 1) / 2)


def _mixer_params(a: dict, hidden: int) -> float:
    gate = a["heads"] if a["gate"] == "headwise" else 0
    return hidden * (a["head_dim"] * (2 * a["heads"] + 2 * a["kv_heads"]) + gate)


def _scores_flops(a: dict, seq: int) -> float:
    """Scores and values of one mixer, forward FLOPs a token, kept keys only."""
    return 2.0 * a["heads"] * 2 * a["head_dim"] * kept_pairs(seq, a["window"] or seq) / seq


def expert_layer_forward_flops(model: dict) -> float:
    """Forward FLOPs a token of ONE expert layer on this chip: the router at
    its published width, the shared expert, and the routed experts AT THE
    ROWS HELD IN EXPECTATION (``num_experts_per_tok`` x held / published:
    10 x 32 / 256 = 1.25 experts a token; the program reports what it was)."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    held = model["num_experts"] / model["router_width"]
    return 2.0 * (hidden * model["router_width"]
                  + 3 * hidden * model["shared_expert_intermediate_size"]
                  + model["num_experts_per_tok"] * held * 3 * hidden * width)


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, lead = model["hidden_size"], lead_layers(model)
    spec, names = kinds(model), layer_kinds(model)
    return {
        "full_scores": sum(_scores_flops(spec[n], seq) for n in names if n == "gqa"),
        "window_scores": sum(_scores_flops(spec[n], seq) for n in names if n == "gqa_win"),
        "projections": sum(2.0 * _mixer_params(spec[n], hidden) for n in names),
        "dense_mlp": lead * 2.0 * 3 * hidden * model["intermediate_size"],
        "experts": (len(names) - lead) * expert_layer_forward_flops(model),
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather is excluded; recomputed operations and the keys a kernel walks and
    does not keep do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


_FLASH_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkdv": 4}


def attention_kernel_costs(model: dict, kind: str, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each attention kernel of a layer
    of ``kind`` (``gqa``: ``flash_*``; ``gqa_win``: ``attn_win_*``) at ``batch``
    rows of ``seq``: {kernel: (flops, bytes)}. A product over a head's D
    features costs 2 D a (query, key) pair: two forward (QK^T, PV), three in
    dQ (also dO V^T, dS K), four in dK/dV (QK^T, P^T dO, dO V^T, dS^T Q).
    Pairs: the band's under a window; half the square for the plain kernels,
    as they count themselves (``ops/trace_log.py::note_flash_cost``). Bytes
    are the operands and results once, in bf16: q-shaped arrays (q, o, dO,
    dQ) at the QUERY heads' count, k and v at the kv heads', the float32
    statistics (the forward's logsumexp over 128 lanes), and dK and dV, which
    leave the kernel at the QUERY heads' count and are summed outside."""
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
    """Every parameter held here: the mixers with their gates, two norms a
    layer, the leading dense MLP, and for an expert layer the router, the
    shared expert and the held experts; both embeddings over the vocabulary's
    slice and the final norm."""
    hidden, lead = model["hidden_size"], lead_layers(model)
    spec = kinds(model)
    total = 0
    for i, n in enumerate(layer_kinds(model)):
        total += int(_mixer_params(spec[n], hidden)) + 2 * hidden
        if i < lead:
            total += 3 * hidden * model["intermediate_size"]
        else:
            total += (model["router_width"] * hidden
                      + 3 * hidden * model["shared_expert_intermediate_size"]
                      + model["num_experts"] * 3 * hidden * model["moe_intermediate_size"])
    return total + 2 * hidden * model["vocab_size"] + hidden
