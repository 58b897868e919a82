"""The yardstick's arithmetic for the decoder of block-selected sparse
attention beside lightning linear attention (MiniCPM-SALA: 32 query heads over
2 kv heads under sets of 64 blocks of 64 keys, one set a kv group, no rope;
32-head linear attention under a decay fixed by head and layer; SwiGLU MLPs;
three fixed multipliers), kept with the benchmark so that it does not move
with the program (``tests/benchmark_suite/test_bm_sala.py`` holds it equal to
``ray_tpu.models.llama.train_flops_per_token`` and to what the kernels record
of themselves, ``kernel_costs()``).

Two counts, kept apart. The MODEL's FLOPs a token (``forward_flops_by_part``,
``train_flops_per_token``: the numerator of ``train.mfu``) count useful work
only: a block-selected layer's scores and values over the keys a query KEEPS
and its selection over the pooled keys it may see, a lightning layer's two
products against its state. The KERNELS' operations and bytes
(``lightning_kernel_costs``, ``select_kernel_costs``: the numerators of their
rooflines) count the work the kernels DO: every tile of the causal triangle
under the sets with the product that spreads its flags, the chunked form's
products inside a chunk; so that no roofline can pass 100.
"""

from __future__ import annotations

import math

from .flops_swa import roofline_seconds

__all__ = ["KIND_OF", "layer_kinds", "period", "kinds", "multipliers", "kept_keys",
           "kept_share", "forced_share", "forward_flops_by_part", "train_flops_per_token",
           "lightning_kernel_costs", "select_kernel_costs", "roofline_seconds", "param_count"]

# ``mixer_types`` entry -> the program's mixer kind
KIND_OF = {"minicpm4": "block_sparse", "lightning-attn": "lightning"}
SIZES = ("kernel_size", "kernel_stride", "block_size", "init_blocks", "window_size", "topk")
# the chunk of ``ops/lightning_attention.py`` and the tiles of ``ops/attention.py``
LIGHTNING_CHUNK = 128
ATTENTION_TILE = 1024


def layer_kinds(model: dict) -> list[str]:
    """The mixer kind of every layer run."""
    assert len(model["mixer_types"]) == model["num_hidden_layers"], "a mixer type a layer"
    return [KIND_OF[m] for m in model["mixer_types"]]


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
    use (``BlockSparseAttention``'s and ``LightningAttention``'s fields)."""
    assert model["qk_norm"] and not model["attn_use_rope"] and model["lightning_use_rope"]
    assert model["attn_use_output_gate"] and model["use_output_gate"] and model["use_output_norm"]
    assert model["lightning_nkv"] == model["lightning_nh"]
    assert model["lightning_scale"] == "1/sqrt(d)" and not model["attention_bias"]
    return {
        "block_sparse": dict(heads=model["num_attention_heads"],
                             kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
                             **{k: model["sparse_config"][k] for k in SIZES}),
        "lightning": dict(heads=model["lightning_nh"], head_dim=model["lightning_head_dim"],
                          rope_theta=float(model["rope_theta"]),
                          depth=model["num_hidden_layers_published"])}


def multipliers(model: dict) -> dict:
    """The three fixed multipliers: the embedding's, a residual branch's (by
    the PUBLISHED depth) and the head's input's."""
    return {"embed_scale": float(model["scale_emb"]),
            "residual_scale": model["scale_depth"] / math.sqrt(
                model["num_hidden_layers_published"]),
            "logit_scale": model["dim_model_base"] / model["hidden_size"]}


def kept_keys(model: dict, seq: int) -> float:
    """Mean keys a query of a ``seq``-long row attends in a block-selected
    layer: ``topk`` blocks (every block while it sees fewer), of its own block
    the keys up to itself."""
    a = kinds(model)["block_sparse"]
    block, total = a["block_size"], 0
    for t in range(seq):
        total += min(t // block + 1, a["topk"]) * block - (block - 1 - t % block)
    return total / seq


def kept_share(model: dict, seq: int) -> float:
    """Attended (query, key) pairs over the causal pairs."""
    return kept_keys(model, seq) * seq / (seq * (seq + 1) / 2)


def forced_share(model: dict, seq: int) -> float:
    """The share of a set's blocks that are the first or hold a key of the
    window, over all queries of a ``seq``-long row: chosen whatever the
    weights."""
    a = kinds(model)["block_sparse"]
    block, forced, held = a["block_size"], 0, 0
    for t in range(seq):
        first = max(t - (a["window_size"] - 1), 0) // block
        forced += t // block - first + 1 + (min(first, a["init_blocks"]))
        held += min(t // block + 1, a["topk"])
    return forced / held


def _mixer_params(kind: str, a: dict, hidden: int) -> int:
    if kind == "lightning":
        return 5 * hidden * a["heads"] * a["head_dim"]           # q, k, v, gate, out
    return hidden * a["head_dim"] * (3 * a["heads"] + 2 * a["kv_heads"])   # q, gate, out; k, v


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part of the model, at the depth run."""
    hidden, names, spec = model["hidden_size"], layer_kinds(model), kinds(model)
    sparse, lightning = spec["block_sparse"], spec["lightning"]
    n_sparse, n_lightning = names.count("block_sparse"), names.count("lightning")
    per_key = 2.0 * sparse["heads"] * sparse["head_dim"]
    return {
        "sparse_scores": n_sparse * per_key * 2 * kept_keys(model, seq),
        "sparse_selection": n_sparse * per_key * seq / sparse["kernel_stride"] / 2,
        "lightning_state": n_lightning * lightning["heads"] * 2 * 2.0 * lightning["head_dim"] ** 2,
        "projections": sum(2.0 * _mixer_params(n, spec[n], hidden) for n in names),
        "mlp": len(names) * 2.0 * 3 * hidden * model["intermediate_size"],
        "head": 2.0 * hidden * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token: 3 x the forward pass's. The embedding
    gather is excluded; recomputed operations, the keys a kernel computes and
    does not keep and the chunked form's own products do not count."""
    return 3.0 * sum(forward_flops_by_part(model, seq).values())


def lightning_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each lightning kernel at ``batch``
    rows of ``seq``: {kernel: (flops, bytes)}, the work the chunked form DOES
    (2 x rows x columns x depth a product, whatever passes it takes). Forward:
    two products inside a chunk of 128 (Q K^T, its decayed scores against V)
    and two against the state (Q S, K^T V). Backward: K^T V again for the
    states, five products inside the chunk, four against the state; it reads
    q, k and v twice and dO once and writes three gradients. bf16 operands."""
    a = kinds(model)["lightning"]
    rows, d = batch * a["heads"] * seq, a["head_dim"]
    inside, state, tensor = 2.0 * rows * LIGHTNING_CHUNK * d, 2.0 * rows * d * d, rows * d * 2
    return {"lightning_fwd": (2 * inside + 2 * state, 4 * tensor),
            "lightning_bwd": (5 * inside + 5 * state, 10 * tensor)}


def select_kernel_costs(model: dict, batch: int, seq: int) -> dict:
    """Operations and bytes of ONE call of each attention kernel under block
    sets (``attn_blk_*``) at ``batch`` rows of ``seq``: {kernel: (flops,
    bytes)}, the work the first form DOES: every 1024 x 1024 tile of the causal
    triangle whole (two products forward, three in dQ, four in dK/dV, over D
    features a pair) and in each tile one more product over the 128 lanes of
    block flags it spreads; bytes are the operands and results once in bf16
    (q-shaped arrays at the query heads, k and v at the kv heads, the float32
    statistics, dK and dV at the query heads' count) and the int8 flags' slab
    a query head."""
    a = kinds(model)["block_sparse"]
    h, kv, d = a["heads"], a["kv_heads"], a["head_dim"]
    tile = max(b for b in range(a["block_size"], min(ATTENTION_TILE, seq) + 1, a["block_size"])
               if seq % b == 0)
    n, n_sets = seq // tile, seq // a["block_size"]
    slab = 128 if n_sets > 128 and n_sets % 128 == 0 else n_sets
    pairs = batch * h * (n * (n + 1) // 2) * tile * tile
    q_b, kv_b, stats = batch * h * seq * d * 2, 2 * batch * kv * seq * d * 2, batch * h * seq * 4
    flags = batch * h * seq * slab
    nbytes = {"fwd": 2 * q_b + kv_b + 128 * stats,
              "bwd_dq": 3 * q_b + kv_b + 2 * stats,
              "bwd_dkdv": 2 * q_b + kv_b + 2 * stats + 2 * q_b}
    return {f"attn_blk_{part}": (2.0 * pairs * (products * d + slab), nbytes[part] + flags)
            for part, products in (("fwd", 2), ("bwd_dq", 3), ("bwd_dkdv", 4))}


def param_count(model: dict) -> int:
    """Every trained parameter held here: a layer's mixer with its norms' weights
    (q and k a head's width; a lightning layer's output norm the hidden
    width), its two block norms, its MLP; both embeddings over the
    vocabulary's slice and the final norm. The decays are no parameters."""
    hidden, spec = model["hidden_size"], kinds(model)
    total = 2 * hidden * model["vocab_size"] + hidden
    for name in layer_kinds(model):
        a = spec[name]
        norms = 2 * a["head_dim"] + (a["heads"] * a["head_dim"] if name == "lightning" else 0)
        total += (_mixer_params(name, a, hidden) + norms + 2 * hidden
                  + 3 * hidden * model["intermediate_size"])
    return total
