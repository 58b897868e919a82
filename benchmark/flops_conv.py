"""The yardstick's arithmetic for the decoder of double-gated short convolutions
beside roped grouped-query attention (LFM2-8B-A1B: 3 taps over 2,048 channels
between a [2048, 6144] and a [2048, 2048] product; 32 query heads over 8 kv heads
of 64 with per-head q/k norms; leading dense layers of 7,168; a chip's share of
32 sigmoid-routed experts of 1,792, top-4; a tied vocabulary), kept with the
benchmark so that it does not move with the program
(``tests/benchmark_suite/test_bm_conv.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` and to the leaves
``init_params`` makes).

Two counts, kept apart. The MODEL's FLOPs a token (``forward_flops_by_part``,
``train_flops_per_token``: the numerator of ``train.mfu``) count useful work
only: the products, attention's scores and values over the causal triangle, the
routed experts AT THE ROWS HELD IN EXPECTATION; the conv's taps and the two
gates (7 FLOPs a channel) are left out, as every elementwise pass is. The
KERNELS' operations and bytes (``flash_kernel_costs``, ``grouped_matmul_costs``:
the numerators of their rooflines) count the work the kernels DO, whole
diagonal blocks included, so that no roofline can pass 100.
"""

from __future__ import annotations

from .flops_swa import roofline_seconds

__all__ = ["KIND_OF", "layer_kinds", "lead_and_period", "attention", "held", "published",
           "forward_flops_by_part", "train_flops_per_token", "flash_kernel_costs",
           "grouped_matmul_costs", "roofline_seconds", "param_count", "bias_count"]

# ``layer_types`` entry -> the program's mixer kind
KIND_OF = {"conv": "sconv", "full_attention": "attn"}


def layer_kinds(model: dict) -> list[str]:
    """The mixer kind of every layer run."""
    assert len(model["layer_types"]) == model["num_hidden_layers"], "a layer type a layer"
    return [KIND_OF[m] for m in model["layer_types"]]


def lead_and_period(model: dict) -> tuple[list[str], list[str]]:
    """(the leading dense layers' mixer kinds, the mixer kinds of one period of
    the layers that follow them: the shortest unit that, repeated, gives
    them)."""
    names, lead = layer_kinds(model), model["num_dense_layers"]
    rest = names[lead:]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and rest == rest[:n] * (len(rest) // n):
            return names[:lead], rest[:n]
    raise ValueError("no layer follows the leading ones")


def attention(model: dict) -> dict:
    """The attention layers' widths, by the names the reference uses."""
    assert model["hidden_size"] % model["num_attention_heads"] == 0
    return dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
                head_dim=model["hidden_size"] // model["num_attention_heads"],
                rope_theta=float(model["rope_theta"]))


def held(model: dict) -> tuple[int, int]:
    """(first, count) of the experts this chip holds, of ``num_experts_published``."""
    first, last = model["experts_held"]
    assert last - first + 1 == model["num_experts"] <= model["num_experts_published"]
    return first, model["num_experts"]


def published(model: dict, **keys) -> dict:
    """``model`` with the cut keys back at their published values: every expert
    held, and ``keys`` (the published depth, order and vocabulary)."""
    return {**model, "num_experts": model["num_experts_published"],
            "experts_held": [0, model["num_experts_published"] - 1], **keys}


def _mixer_params(kind: str, model: dict) -> int:
    hidden, a = model["hidden_size"], attention(model)
    if kind == "sconv":
        return 3 * hidden * hidden + hidden * hidden
    return hidden * a["head_dim"] * (2 * a["heads"] + 2 * a["kv_heads"])


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, names, a = model["hidden_size"], layer_kinds(model), attention(model)
    lead, width = model["num_dense_layers"], model["moe_intermediate_size"]
    share = model["num_experts"] / model["num_experts_published"]
    return {
        "conv_products": names.count("sconv") * 2.0 * _mixer_params("sconv", model),
        "attention_products": names.count("attn") * 2.0 * _mixer_params("attn", model),
        # scores and values over a query's mean seq / 2 causal keys, as the
        # program's ``attn`` kind counts them
        "attention_scores": names.count("attn") * 2.0 * a["heads"] * a["head_dim"] * seq,
        "dense_mlp": lead * 2.0 * 3 * hidden * model["intermediate_size"],
        "router": (len(names) - lead) * 2.0 * hidden * model["num_experts_published"],
        "routed_experts": (len(names) - lead) * 2.0 * model["num_experts_per_tok"] * share
        * 3 * hidden * width,
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather, the conv's taps and its gates are excluded; recomputed operations
    and the pairs above the diagonal do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


def flash_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each plain attention kernel at the
    attention layers' 32 : 8 heads of 64, as ``flops_ssm.flash_kernel_costs``
    counts granite's: half the square's pairs at the query heads' count, 2 / 3
    / 4 products of 2 D a pair; q-shaped arrays at the query heads, k and v at
    the kv heads, dK and dV at the query heads' count as the kernel writes
    them, the float32 statistics."""
    a = attention(model)
    hq, kv, d = a["heads"], a["kv_heads"], a["head_dim"]
    pairs = batch * hq * seq * seq / 2
    q_b, kv_b, stats = batch * hq * seq * d * 2, 2 * batch * kv * seq * d * 2, batch * hq * seq * 4
    nbytes = {"flash_fwd": 2 * q_b + kv_b + 128 * stats,
              "flash_bwd_dq": 3 * q_b + kv_b + 2 * stats,
              "flash_bwd_dkdv": 2 * q_b + kv_b + 2 * stats + 2 * q_b}
    return {name: (2.0 * pairs * products * d, nbytes[name])
            for name, products in (("flash_fwd", 2), ("flash_bwd_dq", 3), ("flash_bwd_dkdv", 4))}


def grouped_matmul_costs(model: dict, rows: float) -> tuple[float, float]:
    """Operations and bytes of ONE grouped-matmul call over ``rows`` rows of the
    held experts: 2 x rows x hidden x expert width whichever of the three
    shapes it has (gate / up, down, a weight's gradient); the rows in and out
    and the held experts' matrix once, in bf16."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    return (2.0 * rows * hidden * width,
            2.0 * (rows * (hidden + width) + model["num_experts"] * hidden * width))


def param_count(model: dict) -> int:
    """Every parameter held here, the routers' selection biases apart (32 a
    layer that no gradient moves: ``bias_count``): a conv layer's two products
    and its taps; an attention layer's four products and its two head norms; a
    layer's two block norms; a leading layer's dense MLP; an expert layer's
    router and held experts; the ONE table over the vocabulary's slice and the
    final norm."""
    hidden = model["hidden_size"]
    assert model["tie_word_embeddings"] and not model["conv_bias"]
    total = hidden * model["vocab_size"] + hidden
    for i, kind in enumerate(layer_kinds(model)):
        own = model["conv_L_cache"] * hidden if kind == "sconv" else 2 * attention(model)["head_dim"]
        total += _mixer_params(kind, model) + own + 2 * hidden
        if i < model["num_dense_layers"]:
            total += 3 * hidden * model["intermediate_size"]
        else:
            total += (hidden * model["num_experts_published"]
                      + model["num_experts"] * 3 * hidden * model["moe_intermediate_size"])
    return total


def bias_count(model: dict) -> int:
    """The selection biases: one a router output and expert layer."""
    assert model["use_expert_bias"]
    return (model["num_hidden_layers"] - model["num_dense_layers"]) * model["num_experts_published"]
