"""Model zoo. Flagship: Llama-3-family decoder built TPU-first — scanned
layers, bf16 params with f32 statistics, logical-axis shardings from
``ray_tpu.parallel``, Pallas flash attention / ring attention. With
``moe_experts > 0`` the MLP is a routed expert layer (``moe.py``: dropless,
the (token, expert) rows sorted by expert over a Pallas grouped matmul);
with ``qk_norm`` q and k are normalised before rope. OLMoE-1B-7B is both."""

from .llama import (
    LlamaConfig,
    PRESETS,
    init_params,
    forward,
    loss_fn,
    param_axes,
)

__all__ = [
    "LlamaConfig",
    "PRESETS",
    "init_params",
    "forward",
    "loss_fn",
    "param_axes",
]
