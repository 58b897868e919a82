"""Rotary position embeddings (RoPE), split-halves convention: plain
frequencies from one ``theta``, or YaRN's blended ones (arXiv 2309.00071)
with its factor on cos and sin."""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, *, theta: float = 500_000.0):
    """Inverse frequencies for each (even) head-dim channel pair."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


@functools.lru_cache(maxsize=None)
def yarn_frequencies(dim: int, *, theta: float, factor: float, original_length: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies for the ``dim`` rotated features of a head,
    float32 [dim / 2]. A pair that turns more than ``beta_fast`` times over
    the ``original_length`` positions the model was trained at keeps its
    frequency ``f_i = theta^(-2i/dim)``, one that turns less than
    ``beta_slow`` times is slowed by ``factor``, and the pairs between are
    blended linearly in i. Made on the host, once a set of arguments: a
    program holds them as a constant, outside any scan."""
    f = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
         ).astype(np.float32)

    def pair_turning(turns: float) -> float:
        return dim * math.log(original_length / (2 * math.pi * turns)) / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(beta_fast)), 0)
    hi = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = (1.0 - ramp).astype(np.float32)
    return (f / np.float32(factor) * (1 - keep) + f * keep).astype(np.float32)


def apply_rope(x, positions, *, theta: float = 500_000.0,
               rotary_dim: int | None = None, inv_freq=None, factor: float = 1.0):
    """Rotate q or k. x: [B, H, S, D]; positions: [B, S] or [S] int32.

    Uses the split-halves convention (rotate_half), matching Llama.
    Computed in f32, cast back to the input dtype. ``rotary_dim`` rotates
    the first that many features of a head (their own split halves, their
    own frequencies) and passes the rest through. ``inv_freq`` [rotated
    features / 2] takes the place of ``theta``'s frequencies
    (``yarn_frequencies``); ``factor`` multiplies cos and sin (YaRN's
    ``attention_factor``).
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rotated = apply_rope(x[..., :rotary_dim], positions, theta=theta,
                             inv_freq=inv_freq, factor=factor)
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    b, h, s, d = x.shape
    if inv_freq is None:
        inv_freq = rope_frequencies(d, theta=theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * inv_freq  # [B,1,S,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
