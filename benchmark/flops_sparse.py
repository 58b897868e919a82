"""The yardstick's arithmetic for the latent-attention decoder with a learned
selection of keys (dots3-note-prev: full layers whose keys an indexer chooses,
window layers, a leading dense layer, a chip's share of sigmoid-routed
experts and of the vocabulary), kept with the benchmark so that it does not
move with the program (``tests/benchmark_suite/test_bm_sparse.py`` holds it
equal to ``ray_tpu.models.llama.train_flops_per_token`` and to what the
kernels record of themselves, ``kernel_costs()``).

USEFUL work only: a full layer's attention counts the keys a query KEEPS
(at most ``index_topk``), a window layer's the band; a kernel that walks more
than that reads a low share of its roofline and can never read over 100.
"""

from __future__ import annotations


def kinds(model: dict) -> dict:
    """The two mixer kinds' widths, by the names the program and the
    reference use (``LatentAttention``'s fields)."""
    full = dict(
        heads=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]), window=0,
        index_heads=model["index_n_heads"], index_dim=model["index_head_dim"],
        index_top_k=model["index_topk"],
        rescale=bool(model["apply_mla_qkv_lora_rescale"]),
        gate=model["attention_gate_type"] == "headwise")
    window = dict(
        heads=model["swa_num_attention_heads"], q_rank=model["swa_q_lora_rank"],
        kv_rank=model["swa_kv_lora_rank"], nope_dim=model["swa_qk_nope_head_dim"],
        rope_dim=model["swa_qk_rope_head_dim"], v_dim=model["swa_v_head_dim"],
        rope_theta=float(model["swa_rope_theta"]), window=model["sliding_window_size"],
        index_heads=0, index_dim=0, index_top_k=0,
        rescale=bool(model["apply_mla_qkv_lora_rescale"]),
        gate=model["swa_attention_gate_type"] == "headwise")
    return {"mla": full, "mla_win": window}


def layer_kinds(model: dict) -> list[str]:
    return ["mla" if t == "full_attention" else "mla_win" for t in model["layer_types"]]


def kept_pairs(seq: int, width: int) -> float:
    """(query, key) pairs of one head over a row of ``seq`` when a query keeps
    at most ``width`` of its causal keys."""
    width = min(width, seq)
    return width * (width + 1) / 2 + (seq - width) * width


def _main_params(a: dict, hidden: int) -> float:
    qk = a["nope_dim"] + a["rope_dim"]
    return (hidden * a["q_rank"] + a["q_rank"] * a["heads"] * qk
            + hidden * (a["kv_rank"] + a["rope_dim"])
            + a["kv_rank"] * a["heads"] * (a["nope_dim"] + a["v_dim"])
            + a["heads"] * a["v_dim"] * hidden + (hidden * a["heads"] if a["gate"] else 0))


def _index_params(a: dict, hidden: int) -> float:
    if not a["index_heads"]:
        return 0.0
    return (a["q_rank"] * a["index_heads"] * a["index_dim"] + hidden * a["index_dim"]
            + hidden * a["index_heads"])


def mixer_forward_flops(a: dict, hidden: int, seq: int) -> float:
    """Forward FLOPs a token of ONE mixer: 2 x its matmul parameters (the
    indexer's at two thirds: its inputs are cut from the graph, so training
    makes its weights' gradients and no input's, 4 N where the rest costs 6 N,
    and everything here is multiplied by 3), scores and values over the keys
    kept, and the index scores over every causal key."""
    width = a["window"] or a["index_top_k"] or seq
    attention = 2.0 * a["heads"] * (a["nope_dim"] + a["rope_dim"] + a["v_dim"]) \
        * kept_pairs(seq, width) / seq
    index = 2.0 * a["index_heads"] * a["index_dim"] * (seq + 1) / 2
    return (2.0 * (_main_params(a, hidden) + _index_params(a, hidden) * 2.0 / 3.0)
            + attention + index)


def expert_layer_forward_flops(model: dict) -> float:
    """Forward FLOPs a token of ONE expert layer on this chip: the router at
    its published width, the shared expert (plain), and the routed experts AT
    THE ROWS HELD IN EXPECTATION (``num_experts_per_tok`` x held / published:
    8 x 8 / 256 = 0.25 experts a token; the program reports what it was)."""
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"] / model["router_width"]
    return 2.0 * (hidden * model["router_width"]
                  + 3 * hidden * width * model["n_shared_experts"]
                  + model["num_experts_per_tok"] * held * 3 * hidden * width)


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, lead = model["hidden_size"], model["first_k_dense_replace"]
    spec, names = kinds(model), layer_kinds(model)
    return {
        "full_attention": sum(mixer_forward_flops(spec[n], hidden, seq)
                              for n in names if n == "mla"),
        "window_attention": sum(mixer_forward_flops(spec[n], hidden, seq)
                                for n in names if n == "mla_win"),
        "dense_mlp": lead * 2.0 * 3 * hidden * model["intermediate_size"],
        "experts": (len(names) - lead) * expert_layer_forward_flops(model),
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather is excluded; recomputed operations, the keys a kernel walks and
    does not keep, and the head-summed probabilities the indexer's target
    needs do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


_WIDTHS = {"fwd": (1, 1), "bwd_dq": (2, 1), "bwd_dkdv": (2, 2)}


def attention_kernel_costs(model: dict, kind: str, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each attention kernel of a layer
    of ``kind`` (``mla``: ``attn_sel_*``; ``mla_win``: ``attn_win_*``) at
    ``batch`` rows of ``seq``: {kernel: (flops, bytes)}. FLOPs are the kept
    pairs': 2 D for each product over the key head's width, 2 Dv for each
    over the value head's (forward QK^T and PV; dQ also dO V^T and dS K; dK/dV
    QK^T, P^T dO, dO V^T, dS^T Q). Bytes are the operands and results once, in
    bf16: q-shaped arrays at D, o and dO at Dv, k and v, the float32
    statistics (the forward's logsumexp over 128 lanes), dK and dV, and for a
    selection the int8 key sets."""
    a = kinds(model)[kind]
    h, d, dv = a["heads"], a["nope_dim"] + a["rope_dim"], a["v_dim"]
    pairs = kept_pairs(seq, a["window"] or a["index_top_k"])
    q_b, o_b = batch * h * seq * d * 2, batch * h * seq * dv * 2
    kv_b, stats = batch * h * seq * (d + dv) * 2, batch * h * seq * 4
    mask = batch * seq * seq if kind == "mla" else 0
    nbytes = {"fwd": q_b + o_b + kv_b + 128 * stats,
              "bwd_dq": 2 * q_b + o_b + kv_b + 2 * stats,
              "bwd_dkdv": q_b + o_b + kv_b + 2 * stats + kv_b}
    name = "attn_sel_" if kind == "mla" else "attn_win_"
    return {name + part: (2.0 * batch * h * pairs * (n_d * d + n_dv * dv), nbytes[part] + mask)
            for part, (n_d, n_dv) in _WIDTHS.items()}


def index_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """The same of the indexer's kernels and of ``dsa_probs``: a [T, T] x Di
    product a head over the causal triangle, once forward and twice in each
    backward kernel (the scores again, then its own); the probabilities are
    one product at the attention's key width over its heads."""
    a = kinds(model)["mla"]
    j, di, t = a["index_heads"], a["index_dim"], seq
    product = 2.0 * batch * j * di * t * (t + 1) / 2
    operands = batch * t * di * 2 * (j + 1) + batch * t * j * 4
    tile = batch * t * t * 4
    d = a["nope_dim"] + a["rope_dim"]
    return {
        "dsa_index_fwd": (product, operands + tile),
        "dsa_index_bwd_dq": (2 * product, operands + tile + batch * j * t * di * 4),
        "dsa_index_bwd_dk": (2 * product, operands + tile),
        "dsa_probs": (2.0 * batch * a["heads"] * d * t * (t + 1) / 2,
                      2 * batch * a["heads"] * t * d * 2 + batch * a["heads"] * t * 4 + tile),
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time one call can take: the larger of its operations over
    the bf16 peak and its bytes over the HBM peak."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def param_count(model: dict) -> int:
    """Every parameter held here: the mixers with their norms, gates and
    indexers (LayerNorm weight and bias), two norms a layer, the leading dense
    MLP, and for an expert layer the router with its bias, the shared expert
    and the held experts; both embeddings over the vocabulary's slice and the
    final norm."""
    hidden, lead = model["hidden_size"], model["first_k_dense_replace"]
    spec, names = kinds(model), layer_kinds(model)
    expert = 3 * hidden * model["moe_intermediate_size"]
    total = 0
    for i, n in enumerate(names):
        a = spec[n]
        total += int(_main_params(a, hidden) + _index_params(a, hidden)) + a["q_rank"] \
            + a["kv_rank"] + (2 * a["index_dim"] if a["index_heads"] else 0) + 2 * hidden
        if i < lead:
            total += 3 * hidden * model["intermediate_size"]
        else:
            total += (model["router_width"] * (hidden + 1)
                      + (model["n_shared_experts"] + model["n_routed_experts"]) * expert)
    return total + 2 * hidden * model["vocab_size"] + hidden
