"""The yardstick's arithmetic: peaks by device kind, operations per token.

``train_flops_per_token`` is a COPY of ``ray_tpu.models.llama.
train_flops_per_token`` for dense decoders (PERF.md lists the original
for a later PR to delete): the yardstick must not move with the program.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip. A device that is not in the table
    is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"benchmark/peaks.json has {sorted(table)}")
    return table[device_kind]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs per trained token of a dense decoder: 6 x the matmul
    parameters (embedding gather excluded) plus causal attention forward
    and backward. Recomputed operations do not count."""
    hidden, layers = model["hidden_size"], model["num_hidden_layers"]
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    head_dim = model["head_dim"]
    per_layer = (hidden * head_dim * (2 * heads + 2 * kv_heads)
                 + 3 * hidden * model["intermediate_size"])
    n_params = layers * per_layer + hidden * model["vocab_size"]
    attention = 6 * layers * heads * head_dim * seq
    return 6.0 * n_params + attention


def param_count(model: dict) -> int:
    """Every parameter, both embeddings and the norms included."""
    hidden, layers = model["hidden_size"], model["num_hidden_layers"]
    per_layer = (hidden * model["head_dim"] * (2 * model["num_attention_heads"]
                                                + 2 * model["num_key_value_heads"])
                 + 3 * hidden * model["intermediate_size"] + 2 * hidden)
    return layers * per_layer + 2 * hidden * model["vocab_size"] + hidden
