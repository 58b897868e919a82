"""Normalization ops. Plain jnp — XLA fuses these into neighbors on TPU;
a hand-written kernel would only duplicate that fusion."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, *, eps: float = 1e-6, offset: float = 0.0):
    """Llama-style RMSNorm, f32 statistics regardless of input dtype.
    ``offset`` 1 is the family that multiplies by ``1 + weight`` and
    starts the weight at zero (Qwen3-Next, Gemma)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    weight = weight.astype(jnp.float32)
    return (x * (weight + offset if offset else weight)).astype(dtype)
