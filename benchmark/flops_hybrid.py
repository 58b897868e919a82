"""The yardstick's arithmetic for the hybrid decoder (Gated DeltaNet layers
beside gated attention, routed experts with a shared one, a chip's share of
the experts and of the vocabulary), kept with the benchmark so that it does
not move with the program (``tests/benchmark_suite/test_bm_hybrid.py`` holds
it equal to ``ray_tpu.models.llama.train_flops_per_token`` and to what the
``gdn_`` kernels record of themselves).
"""

from __future__ import annotations


GDN_CHUNK = 64  # positions the program's chunked delta rule handles at a time


def _gdn_widths(model: dict) -> tuple[int, int]:
    return (model["linear_num_key_heads"] * model["linear_key_head_dim"],
            model["linear_num_value_heads"] * model["linear_value_head_dim"])


def gdn_forward_flops(model: dict) -> float:
    """Forward FLOPs a token of ONE DeltaNet mixer: 2 x its matmul parameters
    (the fused q/k/v/z projection, b and a, the output projection), the conv
    (width multiply-adds a channel), and the gated delta rule ITSELF, per
    value head of D: the three products a token makes against its [D, D]
    state (S^T k, k d^T, S^T q: 3 x 2 D^2). The products a chunked form adds
    inside a chunk, and its inverse, are a program's way of running the rule
    on matrix units and no model FLOP (``gdn_kernel_costs`` counts what the
    kernels do)."""
    hidden = model["hidden_size"]
    kw, vw = _gdn_widths(model)
    vh, d = model["linear_num_value_heads"], model["linear_value_head_dim"]
    matmul = hidden * (2 * kw + 2 * vw + 2 * vh) + vw * hidden
    rule = vh * 3 * 2 * d * d
    conv = 2 * model["linear_conv_kernel_dim"] * (2 * kw + vw)
    return 2.0 * matmul + rule + conv


def attention_forward_flops(model: dict, seq: int) -> float:
    """Forward FLOPs a token of ONE gated attention mixer: q with its gate
    (2 x heads x head_dim columns), k, v and the output projection, plus
    causal scores and values (2 products x 2 heads head_dim seq / 2)."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2.0 * hidden * d * (3 * heads + 2 * kv) + 2.0 * heads * d * seq


def expert_layer_forward_flops(model: dict) -> float:
    """Forward FLOPs a token of ONE expert layer on this chip: the router at
    its published width, the shared expert and its gate, and the routed
    experts AT THE ROWS HELD IN EXPECTATION: ``num_experts_per_tok`` x held /
    published experts' three matrices (10 x 64 / 512 = 1.25 here; with seeded
    weights and uniform ids the held share of the rows is within a few
    percent of 1/8, and the program reports what it was)."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    held = model["num_experts"] / model["router_width"]
    return 2.0 * (hidden * model["router_width"]
                  + 3 * hidden * model["shared_expert_intermediate_size"] + hidden
                  + model["num_experts_per_tok"] * held * 3 * hidden * width)


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    period = model["full_attention_interval"]
    periods = model["num_hidden_layers"] // period
    return {
        "gdn": periods * (period - 1) * gdn_forward_flops(model),
        "attention": periods * attention_forward_flops(model, seq),
        "experts": periods * period * expert_layer_forward_flops(model),
        "head": 2.0 * model["hidden_size"] * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's (a backward
    pass is two of each product). The embedding gather is excluded;
    recomputed operations do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


def gdn_kernel_costs(model: dict, batch: int, seq: int, out_bytes: int = 2) -> dict:
    """Operations and bytes of ONE call of each ``gdn_`` kernel at ``batch``
    rows of ``seq``: {kernel: (flops, bytes)}. The kernels take six float32
    operands a position and value head (three of D features, one of D, one
    row of the [C, C] chunk matrix, and a decay a chunk as a row of D lanes)
    and emit the rule's output in ``out_bytes`` a feature. Forward: three
    products against the state and one inside the chunk. Backward: the
    forward's again for the states, then seven against the state's shape and
    two inside the chunk; it reads the operands twice and the output's
    cotangent once, and writes a float32 gradient for each operand."""
    vh, d, c = model["linear_num_value_heads"], model["linear_value_head_dim"], GDN_CHUNK
    rows = batch * vh * seq
    state, chunk = 2.0 * rows * d * d, 2.0 * rows * c * d
    operands = rows * (3 * d + d + c) * 4 + rows // c * d * 4
    out = rows * d * out_bytes
    return {"gdn_fwd": (3 * state + chunk, operands + out),
            "gdn_bwd": (10 * state + 3 * chunk, 3 * operands + out)}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time one call can take: the larger of its operations over
    the bf16 peak and its bytes over the HBM peak."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def param_count(model: dict) -> int:
    """Every parameter held here: the mixers, the router, the shared expert
    and its gate, the held experts, two norms a layer, both embeddings over
    the vocabulary's slice, the final norm."""
    hidden, period = model["hidden_size"], model["full_attention_interval"]
    periods = model["num_hidden_layers"] // period
    kw, vw = _gdn_widths(model)
    vh, d = model["linear_num_value_heads"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    gdn = (hidden * (2 * kw + 2 * vw + 2 * vh) + (2 * kw + vw) * model["linear_conv_kernel_dim"]
           + 2 * vh + model["linear_value_head_dim"] + vw * hidden)
    attention = hidden * d * (3 * heads + 2 * kv) + 2 * d
    expert = 3 * hidden * model["moe_intermediate_size"]
    rest = (hidden * model["router_width"]
            + 3 * hidden * model["shared_expert_intermediate_size"] + hidden
            + 2 * hidden + model["num_experts"] * expert)
    return (periods * ((period - 1) * (gdn + rest) + attention + rest)
            + 2 * hidden * model["vocab_size"] + hidden)
