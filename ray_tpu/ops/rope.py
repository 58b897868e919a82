"""Rotary position embeddings (RoPE), Llama-3 style with NTK scaling hook."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, *, theta: float = 500_000.0):
    """Inverse frequencies for each (even) head-dim channel pair."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x, positions, *, theta: float = 500_000.0,
               rotary_dim: int | None = None):
    """Rotate q or k. x: [B, H, S, D]; positions: [B, S] or [S] int32.

    Uses the split-halves convention (rotate_half), matching Llama.
    Computed in f32, cast back to the input dtype. ``rotary_dim`` rotates
    the first that many features of a head (their own split halves, their
    own frequencies) and passes the rest through.
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rotated = apply_rope(x[..., :rotary_dim], positions, theta=theta)
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    b, h, s, d = x.shape
    inv_freq = rope_frequencies(d, theta=theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * inv_freq  # [B,1,S,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
