"""The yardstick's arithmetic for a routed (mixture-of-experts) decoder,
kept with the benchmark so that it does not move with the program
(``tests/benchmark_suite/test_bm_moe.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` on the OLMoE configuration).
"""

from __future__ import annotations


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x the matmul parameters one token
    touches (attention projections, the router, its ``num_experts_per_tok``
    experts' three matrices, the output head; the embedding gather
    excluded) plus causal attention forward and backward. Recomputed
    operations do not count, nor do the experts a token is not routed to."""
    hidden, layers = model["hidden_size"], model["num_hidden_layers"]
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    head_dim = model["head_dim"]
    per_layer = (hidden * head_dim * (2 * heads + 2 * kv_heads)
                 + hidden * model["num_experts"]
                 + model["num_experts_per_tok"] * 3 * hidden * model["intermediate_size"])
    n_params = layers * per_layer + hidden * model["vocab_size"]
    attention = 6 * layers * heads * head_dim * seq
    return 6.0 * n_params + attention


def grouped_matmul_flops(model: dict, tokens: int) -> float:
    """FLOPs of ONE grouped-matmul call of a step over ``tokens`` tokens:
    every (token, expert) row against one ``hidden x intermediate`` matrix,
    2 x rows x hidden x intermediate with rows = tokens x experts per token,
    whatever the routing (the dispatch is dropless). The gate, up and down
    products, their input gradients (``moe_gmm``) and their weight
    gradients (``moe_tgmm``) all have this count."""
    rows = tokens * model["num_experts_per_tok"]
    return 2.0 * rows * model["hidden_size"] * model["intermediate_size"]


def param_count(model: dict) -> int:
    """Every parameter: experts, router, attention, the four norms of a
    layer (two of them OLMoE's q and k norms), both embeddings, the final
    norm."""
    hidden, layers = model["hidden_size"], model["num_hidden_layers"]
    q_width = model["head_dim"] * model["num_attention_heads"]
    kv_width = model["head_dim"] * model["num_key_value_heads"]
    per_layer = (hidden * (2 * q_width + 2 * kv_width)
                 + model["num_experts"] * (3 * hidden * model["intermediate_size"] + hidden)
                 + 2 * hidden + q_width + kv_width)
    return layers * per_layer + 2 * hidden * model["vocab_size"] + hidden
