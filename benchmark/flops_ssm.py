"""The yardstick's arithmetic for the decoder of Mamba-2 state-space layers beside
un-roped grouped-query attention (granite-4.0-h-micro: 64 heads of 64 features
over a 128-wide state, one B and one C for all heads, a conv of 4 taps; 32
query heads over 8 kv heads of 64 whose softmax scale is a published constant;
SwiGLU MLPs; a tied vocabulary; four fixed multipliers), kept with the
benchmark so that it does not move with the program
(``tests/benchmark_suite/test_bm_ssm.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` and to what the kernels record
of themselves, ``kernel_costs()``).

Two counts, kept apart. The MODEL's FLOPs a token (``forward_flops_by_part``,
``train_flops_per_token``: the numerator of ``train.mfu``) count useful work
only: a state-space layer's two products against its state, the attention
layer's scores and values over the causal triangle. The KERNELS' operations
and bytes (``ssd_kernel_costs``, ``flash_kernel_costs``: the numerators of
their rooflines) count the work the kernels DO: the chunked form's products
inside a chunk, whole diagonal blocks; so that no roofline can pass 100.
"""

from __future__ import annotations

from .flops_swa import roofline_seconds

__all__ = ["KIND_OF", "layer_kinds", "period", "kinds", "multipliers", "forward_flops_by_part",
           "train_flops_per_token", "ssd_kernel_costs", "flash_kernel_costs", "roofline_seconds",
           "param_count"]

# ``layer_types`` entry -> the program's mixer kind
KIND_OF = {"mamba": "mamba2", "attention": "gqa"}
# what ``ops/ssd.py``'s backward kernel may keep of a head group's chunk states
SSD_BWD_STATE_BYTES = 32 * 1024 * 1024
LANES = 128


def layer_kinds(model: dict) -> list[str]:
    """The mixer kind of every layer run."""
    assert len(model["layer_types"]) == model["num_hidden_layers"], "a layer type a layer"
    return [KIND_OF[m] for m in model["layer_types"]]


def period(model: dict) -> list[str]:
    """The mixer kinds of one period of the stack: the shortest unit that,
    repeated, gives the layers."""
    names = layer_kinds(model)
    for n in range(1, len(names) + 1):
        if len(names) % n == 0 and names == names[:n] * (len(names) // n):
            return names[:n]
    raise ValueError("no layer")


def kinds(model: dict) -> dict:
    """The two mixer kinds' widths, by the names the program and the reference
    use (``Mamba2``'s and ``GroupedQueryAttention``'s fields)."""
    assert model["position_embedding_type"] == "nope" and not model["attention_bias"]
    assert model["mamba_conv_bias"] and not model["mamba_proj_bias"]
    assert model["mamba_expand"] * model["hidden_size"] == (
        model["mamba_n_heads"] * model["mamba_d_head"])
    assert model["normalization_function"] == "rmsnorm" and model["hidden_act"] == "silu"
    return {
        "mamba2": dict(heads=model["mamba_n_heads"], head_dim=model["mamba_d_head"],
                       state=model["mamba_d_state"], groups=model["mamba_n_groups"],
                       conv=model["mamba_d_conv"], chunk=model["mamba_chunk_size"]),
        "gqa": dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
                    head_dim=model["hidden_size"] // model["num_attention_heads"],
                    rope_theta=0.0, softmax_scale=float(model["attention_multiplier"]))}


def multipliers(model: dict) -> dict:
    """Granite's other three multipliers: the embedding's, a residual
    branch's and the head's input's (the logits are DIVIDED by
    ``logits_scaling``)."""
    return {"embed_scale": float(model["embedding_multiplier"]),
            "residual_scale": float(model["residual_multiplier"]),
            "logit_scale": 1.0 / model["logits_scaling"]}


def _mixer_params(kind: str, a: dict, hidden: int) -> int:
    if kind == "mamba2":
        inner = a["heads"] * a["head_dim"]
        return hidden * (2 * inner + 2 * a["groups"] * a["state"] + a["heads"]) + inner * hidden
    return hidden * a["head_dim"] * (2 * a["heads"] + 2 * a["kv_heads"])


def _mlp_width(model: dict) -> int:
    assert model["num_local_experts"] == 0 and model["num_experts_per_tok"] == 0
    return model["shared_intermediate_size"]


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, names, spec = model["hidden_size"], layer_kinds(model), kinds(model)
    ssm, gqa = spec["mamba2"], spec["gqa"]
    return {
        # x (x) B into a head's [P, N] state and S C out of it: 2 x 2 P N
        "ssm_state": names.count("mamba2") * ssm["heads"] * 4.0 * ssm["head_dim"] * ssm["state"],
        # scores and values over a query's mean (seq + 1) / 2 causal keys
        "attention_scores": names.count("gqa") * 2.0 * gqa["heads"] * 2 * gqa["head_dim"]
        * (seq + 1) / 2,
        "projections": sum(2.0 * _mixer_params(n, spec[n], hidden) for n in names),
        "mlp": len(names) * 2.0 * 3 * hidden * _mlp_width(model),
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather and the conv's taps are excluded; recomputed operations and the
    chunked form's own products do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


def ssd_bwd_groups(a: dict, seq: int) -> int:
    """Head groups of ``ssd_bwd`` at ``seq`` positions: the fewest whose chunk
    states [chunks, tiles, 128, N] float32 fit ``SSD_BWD_STATE_BYTES``, a group
    whole sublane tiles of heads (``ops/ssd.py::bwd_group_tiles``)."""
    r = max(LANES // a["head_dim"], 1)
    tiles, n_chunks = a["heads"] // r, seq // a["chunk"]
    fits = [g for g in range(1, tiles + 1) if tiles % g == 0
            and (g == tiles or (g * r) % 8 == 0)
            and g * n_chunks * r * a["head_dim"] * a["state"] * 4 <= SSD_BWD_STATE_BYTES]
    return tiles // max(fits)


def ssd_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each scan kernel at ``batch`` rows of
    ``seq``: {kernel: (flops, bytes)}, the work the chunked form DOES over a
    head's own features (2 x rows x columns x depth a product, whatever passes
    it takes). Forward: ``C B^T`` once a chunk, a head's decayed scores against
    X, ``C S^T`` and ``X^T B``; x in and y out in bf16, B and C, dt and the
    running sums in float32. Backward: ``X^T B`` again for the states; ``C B^T``
    and dG's two products once a chunk and head group; a head's dY X^T and its
    scores against dY inside the chunk, six products against the state; it
    reads x, B, C, dt and the sums twice and dY once and writes dx, two rows of
    gradients a head and a group's dB and dC in float32."""
    a = kinds(model)["mamba2"]
    h, p, n, q = a["heads"], a["head_dim"], a["state"], a["chunk"]
    groups = ssd_bwd_groups(a, seq)
    inside, state = 2.0 * batch * seq * q * p * h, 2.0 * batch * seq * p * n * h
    shared = 2.0 * batch * seq * q * n
    x_b, bc_b, rows_b = batch * seq * h * p * 2, 2 * batch * seq * n * 2, 2 * batch * h * seq * 4
    return {"ssd_fwd": (shared + inside + 2 * state, 2 * x_b + bc_b + rows_b),
            "ssd_bwd": (3 * shared * groups + 2 * inside + 7 * state,
                        4 * x_b + 2 * groups * bc_b + 3 * rows_b
                        + groups * 2 * batch * seq * n * 4)}


def flash_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each plain attention kernel at the
    attention layer's 32 : 8 heads of 64, as ``flops_swa.attention_kernel_costs``
    counts a full layer: half the square's pairs at the query heads' count, 2 /
    3 / 4 products of 2 D a pair; q-shaped arrays at the query heads, k and v at
    the kv heads, dK and dV at the query heads' count as the kernel writes
    them, the float32 statistics."""
    a = kinds(model)["gqa"]
    hq, kv, d = a["heads"], a["kv_heads"], a["head_dim"]
    pairs = batch * hq * seq * seq / 2
    q_b, kv_b, stats = batch * hq * seq * d * 2, 2 * batch * kv * seq * d * 2, batch * hq * seq * 4
    nbytes = {"flash_fwd": 2 * q_b + kv_b + 128 * stats,
              "flash_bwd_dq": 3 * q_b + kv_b + 2 * stats,
              "flash_bwd_dkdv": 2 * q_b + kv_b + 2 * stats + 2 * q_b}
    return {name: (2.0 * pairs * products * d, nbytes[name])
            for name, products in (("flash_fwd", 2), ("flash_bwd_dq", 3), ("flash_bwd_dkdv", 4))}


def param_count(model: dict) -> int:
    """Every trained parameter held here: a state-space layer's products, its
    conv's taps and biases, dt_bias, A_log and D a head, its gated norm's
    weight; an attention layer's four products; a layer's two block norms and
    its MLP; the ONE table over the vocabulary's slice and the final norm."""
    hidden, spec = model["hidden_size"], kinds(model)
    total = hidden * model["vocab_size"] + hidden
    assert model["tie_word_embeddings"]
    for name in layer_kinds(model):
        a = spec[name]
        own = 0
        if name == "mamba2":
            inner = a["heads"] * a["head_dim"]
            channels = inner + 2 * a["groups"] * a["state"]
            own = channels * a["conv"] + channels + 3 * a["heads"] + inner
        total += (_mixer_params(name, a, hidden) + own + 2 * hidden
                  + 3 * hidden * _mlp_width(model))
    return total
