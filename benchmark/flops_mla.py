"""The yardstick's arithmetic for the latent-attention decoder that attends
every causal key (Kimi-K2-Instruct: 64 heads of 128 + 64 query and key
features and 128 value features under YaRN's softmax factor, a leading dense
layer, a chip's share of sigmoid-routed experts under a routed scale and of
the vocabulary), kept with the benchmark so that it does not move with the
program (``tests/benchmark_suite/test_bm_mla.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` and to what the kernels record
of themselves, ``kernel_costs()``).

USEFUL work only: attention counts the causal triangle's pairs, T (T + 1) / 2
a head, as the kernels count themselves; the plain kernels compute whole
diagonal blocks, so none can read over 100 of its roofline.
"""

from __future__ import annotations

import math

from .flops_sparse import roofline_seconds

__all__ = ["yarn", "spec", "causal_pairs", "roofline_seconds",
           "mixer_params", "expert_layer_forward_flops", "forward_flops_by_part",
           "train_flops_per_token", "attention_kernel_costs", "param_count"]

KIND = "mla_full"


def _mscale(factor: float, m: float) -> float:
    """YaRN's ``mscale(F, m) = 0.1 m ln F + 1`` (1 for F <= 1)."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(model: dict) -> tuple[dict, float]:
    """A ``rope_scaling`` group of type ``yarn`` in the form latent attention
    publishes: (``Yarn``'s fields, the softmax factor). cos and sin are
    multiplied by ``mscale(F, mscale) / mscale(F, mscale_all_dim)`` and the
    softmax scale by ``mscale(F, mscale_all_dim)^2``."""
    group = model["rope_scaling"]
    assert group["type"] == "yarn"
    factor = float(group["factor"])
    all_dim = _mscale(factor, float(group["mscale_all_dim"]))
    fields = dict(factor=factor,
                  original_length=int(group["original_max_position_embeddings"]),
                  beta_fast=float(group["beta_fast"]), beta_slow=float(group["beta_slow"]),
                  attention_factor=_mscale(factor, float(group["mscale"])) / all_dim)
    return fields, all_dim ** 2


def spec(model: dict) -> dict:
    """The mixer's widths, by the names the program and the reference use
    (``LatentAttention``'s fields; ``yarn`` a dict of ``Yarn``'s)."""
    fields, softmax_factor = yarn(model)
    return dict(
        heads=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]), window=0, index_heads=0, index_dim=0,
        index_top_k=0, rescale=False, gate=False, yarn=fields, softmax_factor=softmax_factor)


def causal_pairs(seq: int) -> float:
    """(query, key) pairs of one head over a row of ``seq``: s <= t."""
    return seq * (seq + 1) / 2


def mixer_params(model: dict) -> int:
    """One mixer's matmul parameters: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    a, hidden = spec(model), model["hidden_size"]
    qk = a["nope_dim"] + a["rope_dim"]
    return (hidden * a["q_rank"] + a["q_rank"] * a["heads"] * qk
            + hidden * (a["kv_rank"] + a["rope_dim"])
            + a["kv_rank"] * a["heads"] * (a["nope_dim"] + a["v_dim"])
            + a["heads"] * a["v_dim"] * hidden)


def _scores_flops(model: dict, seq: int) -> float:
    """Scores and values of one mixer, forward FLOPs a token."""
    a = spec(model)
    return 2.0 * a["heads"] * (a["nope_dim"] + a["rope_dim"] + a["v_dim"]) \
        * causal_pairs(seq) / seq


def expert_layer_forward_flops(model: dict) -> dict:
    """Forward FLOPs a token of ONE expert layer on this chip, by part: the
    router at its published width, the shared expert, and the routed experts
    AT THE ROWS HELD IN EXPECTATION (``num_experts_per_tok`` x held /
    published: 8 x 8 / 384 = 1/6 of an expert a token; the program reports
    what it was)."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"] / model["router_width"]
    return {"router": 2.0 * hidden * model["router_width"],
            "shared_expert": 2.0 * 3 * hidden * width * model["n_shared_experts"],
            "routed_experts": 2.0 * model["num_experts_per_tok"] * held * 3 * hidden * width}


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, lead = model["hidden_size"], model["first_k_dense_replace"]
    layers = model["num_hidden_layers"]
    experts = expert_layer_forward_flops(model)
    return {
        "scores": layers * _scores_flops(model, seq),
        "projections": layers * 2.0 * mixer_params(model),
        "dense_mlp": lead * 2.0 * 3 * hidden * model["intermediate_size"],
        **{k: (layers - lead) * v for k, v in experts.items()},
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather is excluded; recomputed operations and the pairs above the
    diagonal that a kernel's diagonal blocks compute do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


# products over the key head's width and over the value head's, a pair:
# forward QK^T and PV; dQ also dO V^T and dS K; dK/dV QK^T, P^T dO, dO V^T, dS^T Q
_WIDTHS = {"fwd": (1, 1), "bwd_dq": (2, 1), "bwd_dkdv": (2, 2)}


def attention_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each plain flash kernel of a layer
    at ``batch`` rows of ``seq``: {kernel: (flops, bytes)}. A product over the
    key head's D = 192 features costs 2 D a (query, key) pair, one over the
    value head's Dv = 128 costs 2 Dv: 2 (D + Dv) forward, 2 (2 D + Dv) in dQ,
    2 (2 D + 2 Dv) in dK/dV, over the causal triangle's pairs at the heads'
    count (query heads = key heads). Bytes are the operands and results once,
    in bf16: q, k, dQ and dK at D, v, o, dO and dV at Dv, and the float32
    statistics (the forward's logsumexp over 128 lanes; lse and delta compact
    into the backward kernels), as ``ops/trace_log.py::note_attention_cost``
    records of a plain kernel whose value head is narrower than its key head."""
    a = spec(model)
    h, d, dv = a["heads"], a["nope_dim"] + a["rope_dim"], a["v_dim"]
    pairs = causal_pairs(seq)
    q_b, o_b = batch * h * seq * d * 2, batch * h * seq * dv * 2
    kv_b, stats = batch * h * seq * (d + dv) * 2, batch * h * seq * 4
    nbytes = {"fwd": q_b + o_b + kv_b + 128 * stats,
              "bwd_dq": 2 * q_b + o_b + kv_b + 2 * stats,
              "bwd_dkdv": q_b + o_b + kv_b + 2 * stats + kv_b}
    return {"flash_" + part: (2.0 * batch * h * pairs * (n_d * d + n_dv * dv), nbytes[part])
            for part, (n_d, n_dv) in _WIDTHS.items()}


def param_count(model: dict) -> int:
    """Every parameter held here: the mixers with their two latent norms, two
    norms a layer, the leading dense MLP, and for an expert layer the router
    with its bias, the shared expert and the held experts; both embeddings
    over the vocabulary's slice and the final norm."""
    hidden, lead = model["hidden_size"], model["first_k_dense_replace"]
    expert = 3 * hidden * model["moe_intermediate_size"]
    total = 0
    for i in range(model["num_hidden_layers"]):
        total += mixer_params(model) + model["q_lora_rank"] + model["kv_lora_rank"] + 2 * hidden
        if i < lead:
            total += 3 * hidden * model["intermediate_size"]
        else:
            total += (model["router_width"] * (hidden + 1)
                      + (model["n_shared_experts"] + model["n_routed_experts"]) * expert)
    return total + 2 * hidden * model["vocab_size"] + hidden
